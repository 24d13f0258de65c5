"""Plain float32 reference of the dense decoder (``models.transformer``).

The correctness oracle for chip runs: the published architecture written
out once more in straightforward ``jax.numpy`` — LayerNorm or RMSNorm,
NeoX-style partial rotary embedding, causal grouped-query softmax attention,
gated or plain FFN, untied or tied unembedding — with no kernels, no cache,
no batching tricks and no code shared with the serving path.  Every weight
is upcast to float32 and every matmul runs under
``jax.default_matmul_precision("highest")``, because a TPU otherwise runs
float32 matmuls in bfloat16 passes.

It runs one layer at a time, each layer its own jitted call: only one
layer's weights are ever upcast at once, so a model whose float32 copy
would not fit next to its bfloat16 weights still gets a reference, and all
layers share one compiled program.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _norm(kind: str, p: dict, x):
    if kind in ("layernorm", "nonparam_ln"):
        mu = x.mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(((x - mu) ** 2).mean(-1, keepdims=True) + 1e-5)
        return y * p["scale"] + p["bias"] if kind == "layernorm" else y
    if kind == "rmsnorm":  # scale stored as an offset from one
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (1.0 + p["scale"])
    raise ValueError(kind)


def _rope(x, theta: float, rotary_dim: int):
    """x (B, S, H, D): rotate the first ``rotary_dim`` channels, halves
    paired NeoX-style (channel i with i + rotary_dim / 2)."""
    S = x.shape[1]
    half = rotary_dim // 2
    inv = 1.0 / theta ** (np.arange(0, rotary_dim, 2, dtype=np.float32) / rotary_dim)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv  # (S, half)
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _act(name: str, x):
    return {"silu": jax.nn.silu, "relu": jax.nn.relu,
            "gelu": jax.nn.gelu,
            "gelu_tanh": lambda v: jax.nn.gelu(v, approximate=True)}[name](x)


@functools.partial(jax.jit, static_argnames="cfg")
def _layer(cfg, p: dict, x):
    p = _f32(p)
    B, S, _ = x.shape
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a = p["attn"]
    h = _norm(cfg.norm, p["ln1"], x)
    q = (h @ a["wq"] + a.get("bq", 0.0)).reshape(B, S, Hq, D)
    k = (h @ a["wk"] + a.get("bk", 0.0)).reshape(B, S, Hkv, D)
    v = (h @ a["wv"] + a.get("bv", 0.0)).reshape(B, S, Hkv, D)
    if cfg.qk_norm:
        q = _norm("rmsnorm", {"scale": a["q_norm"]}, q)
        k = _norm("rmsnorm", {"scale": a["k_norm"]}, k)
    rd = int(cfg.rotary_pct * D)
    q, k = _rope(q, cfg.rope_theta, rd), _rope(k, cfg.rope_theta, rd)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    pos = np.arange(S)
    keep = pos[None, :] <= pos[:, None]
    if cfg.window is not None:
        keep &= pos[:, None] - pos[None, :] < cfg.window
    s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(B, S, Hq * D) @ a["wo"]
    h = _norm(cfg.norm, p["ln2"], x)
    m = p["mlp"]
    if cfg.gated_ffn:
        f = _act(cfg.act, h @ m["w_gate"]) * (h @ m["w_up"])
    else:
        f = _act(cfg.act, h @ m["w_up"] + m.get("b_up", 0.0))
    return x + f @ m["w_down"] + m.get("b_down", 0.0)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames="cfg")
def _head(cfg, p: dict, x):
    p = _f32(p)
    x = _norm(cfg.norm, p["final_norm"], x)
    w = p["embed"]["table"].T if cfg.tie_embeddings else p["lm_head"]["w"]
    logits = x @ w
    if cfg.logit_softcap is not None:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def dense_lm_logits(cfg, params: dict, tokens) -> jax.Array:
    """tokens (B, S) -> float32 logits (B, S, padded_vocab) of a
    ``transformer.DenseLMConfig`` model with per-layer params
    (``scan_layers=False``)."""
    if cfg.scan_layers:
        raise ValueError("the reference walks per-layer params: "
                         "scan_layers=False")
    cfg = dataclasses.replace(cfg, dtype=F32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["table"], tokens)
        for i in range(cfg.n_layers):
            x = _layer(cfg, params["blocks"][str(i)], x)
        head = {"final_norm": params["final_norm"]}
        if cfg.tie_embeddings:
            head["embed"] = params["embed"]
        else:
            head["lm_head"] = params["lm_head"]
        return _head(cfg, head, x)
