"""Decoder-only transformers (StableLM-2 and its kind): sizes, weights,
the program's config object, and the work of a step.

Plain reference: ``bench/references/dense.py``.  The program runs them
through ``repro.models.transformer`` under the ``dense`` adapter.
"""
from __future__ import annotations

import dataclasses

import jax

from bench import costs, model
from bench.weights import normal


@dataclasses.dataclass(frozen=True)
class Dense(model.Model):
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    rotary_pct: float = 1.0
    rope_theta: float = 1e4
    qkv_bias: bool = False


def from_config(cfg: dict) -> Dense:
    heads = cfg["num_attention_heads"]
    return Dense(**model.common(cfg), n_heads=heads,
                 n_kv_heads=cfg["num_key_value_heads"],
                 head_dim=cfg["hidden_size"] // heads,
                 d_ff=cfg["intermediate_size"],
                 rotary_pct=cfg["partial_rotary_factor"],
                 rope_theta=float(cfg["rope_theta"]),
                 qkv_bias=cfg["use_qkv_bias"])


# -- weights ------------------------------------------------------------------


def trunk(m: Dense, key) -> dict:
    d, H, D, F, V = m.d_model, m.n_heads, m.head_dim, m.d_ff, m.vocab
    dt = m.dtype
    keys = iter(jax.random.split(key, 1 + 16 * m.n_layers))
    blocks = {}
    for i in range(m.n_layers):
        attn = {
            "wq": normal(next(keys), (d, H * D), d ** -0.5, dt),
            "wk": normal(next(keys), (d, m.n_kv_heads * D), d ** -0.5, dt),
            "wv": normal(next(keys), (d, m.n_kv_heads * D), d ** -0.5, dt),
            "wo": normal(next(keys), (H * D, d), (H * D) ** -0.5, dt),
        }
        if m.qkv_bias:
            attn["bq"] = normal(next(keys), (H * D,), 0.02, dt)
            attn["bk"] = normal(next(keys), (m.n_kv_heads * D,), 0.02, dt)
            attn["bv"] = normal(next(keys), (m.n_kv_heads * D,), 0.02, dt)
        blocks[str(i)] = {
            "attn": attn,
            "mlp": {"w_gate": normal(next(keys), (d, F), d ** -0.5, dt),
                    "w_up": normal(next(keys), (d, F), d ** -0.5, dt),
                    "w_down": normal(next(keys), (F, d), F ** -0.5, dt)},
            "ln1": {"scale": 1.0 + normal(next(keys), (d,), 0.02, dt),
                    "bias": normal(next(keys), (d,), 0.02, dt)},
            "ln2": {"scale": 1.0 + normal(next(keys), (d,), 0.02, dt),
                    "bias": normal(next(keys), (d,), 0.02, dt)},
        }
    return {"embed": {"table": normal(next(keys), (V, d), 1.0, dt)},
            "blocks": blocks}


def head(m: Dense, key) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d = m.d_model
    return {"final_norm": {"scale": 1.0 + normal(k1, (d,), 0.02, m.dtype),
                           "bias": normal(k2, (d,), 0.02, m.dtype)},
            "lm_head": {"w": normal(k3, (d, m.vocab), d ** -0.5, m.dtype)}}


def program_config(m: Dense, program: dict):
    """The program's ``DenseLMConfig`` for ``m``; ``program["fields"]``
    sets the rest (norm, activation, layer scan, ...)."""
    from repro.models.transformer import DenseLMConfig

    return DenseLMConfig(
        name=program["name"], n_layers=m.n_layers, d_model=m.d_model,
        n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, head_dim=m.head_dim,
        d_ff=m.d_ff, vocab_size=m.vocab, rotary_pct=m.rotary_pct,
        rope_theta=m.rope_theta, qkv_bias=m.qkv_bias, dtype=m.dtype,
        **program["fields"])


# -- work -----------------------------------------------------------------------


def token_flops(m: Dense, context: int) -> int:
    """Matmul FLOPs of one token through the trunk; ``context`` is the
    number of positions it attends to (itself included)."""
    d, hd = m.d_model, m.n_heads * m.head_dim
    kvd = m.n_kv_heads * m.head_dim
    per_layer = (2 * d * hd + 4 * d * kvd + 2 * hd * d + 6 * d * m.d_ff
                 + 4 * hd * context)
    return m.n_layers * per_layer


def score_work(m: Dense, rows: int, seq: int) -> dict:
    """The trunk's kernels in one scoring call over ``rows`` sequences of
    ``seq`` tokens: one ``flash_attention`` a layer."""
    fb = costs.flash_attention(rows, seq, m.n_heads, m.n_kv_heads, m.head_dim,
                               m.dtype.itemsize)
    return {"flash_attention": (fb[0] * m.n_layers, fb[1] * m.n_layers)}


def decode_work(m: Dense, lengths, page_size: int) -> dict:
    """The trunk's kernels in one paged decode step whose real rows attend
    ``lengths`` positions (the new token included): a layer gathers the
    pages each row holds, once for K and once for V, and runs one
    ``decode_attention``."""
    item = m.dtype.itemsize
    pages = sum(-(-int(n) // page_size) for n in lengths)
    page_bytes = page_size * m.n_kv_heads * m.head_dim * item
    att = costs.decode_attention(lengths, m.n_heads, m.n_kv_heads, m.head_dim,
                                 item)
    gather = costs.page_gather(pages, page_bytes)
    L = m.n_layers
    return {"decode_attention": (att[0] * L, att[1] * L),
            "page_gather": (0, gather[1] * 2 * L)}
