"""Per-arch smoke tests + model-level semantics.

Every assigned architecture instantiates its REDUCED config and runs one
forward + one train step on CPU, asserting output shapes and finiteness.
Decode paths are checked for exact consistency with the full forward.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeSpec, input_specs
from repro.configs.registry import all_arch_ids, load_arch
from repro.models import layers as L
from repro.models.registry import get_family
from repro.train.optimizer import AdamW
from repro.train.trainer import init_state, make_train_step

ARCHS = all_arch_ids()


def _smoke_batch(cfg, family, key, batch=2, seq=32):
    spec = ShapeSpec("t", seq, batch, "train")
    specs = input_specs(cfg, family, spec)
    out = {}
    for k, s in specs.items():
        if s.dtype == jnp.int32:
            out[k] = jax.random.randint(key, s.shape, 0, cfg.vocab_size)
        else:
            out[k] = jax.random.normal(key, s.shape, jnp.float32)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch, rng):
    mod = load_arch(arch)
    cfg = mod.smoke_config()
    fam = get_family(mod.FAMILY)
    params = fam.init(cfg, rng)
    batch = _smoke_batch(cfg, mod.FAMILY, rng)

    loss = fam.loss(cfg, params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"

    opt = AdamW(lr=1e-3)
    step = make_train_step(lambda p, b: fam.loss(cfg, p, b), opt)
    state = init_state(params, opt)
    state, metrics = jax.jit(step)(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params changed
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(state["params"])[0]
    assert not np.allclose(np.asarray(l0), np.asarray(l1))


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-14b", "falcon-mamba-7b",
                                  "recurrentgemma-9b", "seamless-m4t-medium",
                                  "deepseek-moe-16b"])
def test_decode_matches_forward(arch, rng):
    """Prefill(prompt) + decode(1 token) logits == forward(prompt+token).

    MoE configs get a generous capacity factor: with realistic capacity the
    *same* token routes differently in a 9-token forward vs. a 1-token decode
    (capacity competition) — inherent to capacity-based MoE, not a bug."""
    import dataclasses as _dc

    mod = load_arch(arch)
    cfg = mod.smoke_config()
    if mod.FAMILY == "moe":
        cfg = _dc.replace(cfg, capacity_factor=8.0)
    fam = get_family(mod.FAMILY)
    params = fam.init(cfg, rng)
    B, S = 2, 8
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab_size)
    prompt, nxt = toks[:, :S], toks[:, S:]

    if mod.FAMILY == "encdec":
        src = jax.random.normal(rng, (B, 4, cfg.d_model), jnp.float32)
        full = fam.forward(cfg, params, src, toks)
        _, cache = fam.prefill(cfg, params, src, prompt, S + 4)
        step_logits, _ = fam.decode_step(cfg, params, cache, nxt)
        ref = full[:, -1]
    elif mod.FAMILY == "moe":
        full, _ = fam.forward(cfg, params, toks)
        _, cache = fam.prefill(cfg, params, prompt, S + 4)
        step_logits, _ = fam.decode_step(cfg, params, cache, nxt)
        ref = full[:, -1]
    else:
        full = fam.forward(cfg, params, toks)
        _, cache = fam.prefill(cfg, params, prompt, S + 4)
        step_logits, _ = fam.decode_step(cfg, params, cache, nxt)
        ref = full[:, -1]
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_blocked_attention_matches_dense(rng):
    """The O(S*(W+bq)) sliding-window path == the dense masked oracle."""
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 16
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    for window in [None, 16]:
        blocked = L.blocked_causal_attention(q, k, v, pos, window=window, block_q=16)
        dense = L.gqa_attention(q, k, v, L.attention_mask(pos, pos, True, window))
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5)


def test_vocab_padding_masked_out(rng):
    from repro.models import transformer as T

    cfg = T.DenseLMConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          head_dim=16, d_ff=64, vocab_size=300)
    assert cfg.padded_vocab == 512
    params = T.init(cfg, rng)
    toks = jax.random.randint(rng, (2, 9), 0, 300)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss = T.loss_fn(cfg, params, batch)
    # CE upper-bounded by log(V_real), not log(V_padded), for uniform logits
    assert float(loss) < np.log(512) + 1.0


def test_mamba_chunked_scan_matches_unchunked(rng):
    from repro.models import ssm as S

    cfg_c = S.MambaConfig(n_layers=2, d_model=32, d_inner=64, d_state=8,
                          dt_rank=4, vocab_size=128, chunk=4)
    cfg_u = S.MambaConfig(n_layers=2, d_model=32, d_inner=64, d_state=8,
                          dt_rank=4, vocab_size=128, chunk=16)
    p = S.init(cfg_c, rng)
    toks = jax.random.randint(rng, (2, 16), 0, 128)
    np.testing.assert_allclose(
        np.asarray(S.forward(cfg_c, p, toks)),
        np.asarray(S.forward(cfg_u, p, toks)),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.slow
def test_griffin_ring_buffer_long_decode(rng):
    """Decode far past the window: ring buffer must match a fresh forward."""
    from repro.models import griffin as G

    cfg = G.GriffinConfig(n_layers=3, d_model=32, d_rnn=32, n_heads=2,
                          n_kv_heads=1, head_dim=16, d_ff=64, vocab_size=128,
                          window=4, chunk=4)
    p = G.init(cfg, rng)
    T_ = 12  # 3x the window
    toks = jax.random.randint(rng, (1, T_), 0, 128)
    cache = G.init_cache(cfg, 1, max_len=T_)
    outs = []
    for t in range(T_):
        lg, cache = G.decode_step(cfg, p, cache, toks[:, t : t + 1])
        outs.append(lg[:, 0])
    full = G.forward(cfg, p, toks)
    np.testing.assert_allclose(np.asarray(outs[-1]), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("variant", [
    {},
    dict(norm="rmsnorm", tie_embeddings=True, n_kv_heads=2, qkv_bias=True,
         qk_norm=True),
    dict(norm="nonparam_ln", window=5, gated_ffn=False, logit_softcap=30.0,
         rotary_pct=0.25),
], ids=["stablelm_like", "tied_gqa_qknorm", "windowed_softcap"])
def test_f32_reference_matches_dense_forward(variant, rng):
    """``models.reference`` (the chip run's oracle, written independently of
    the model code) agrees with ``transformer.forward`` in float32."""
    from repro.models import reference
    from repro.models import transformer as T

    kw = dict(variant)
    cfg = T.DenseLMConfig(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=kw.pop("n_kv_heads", 4),
        head_dim=16, d_ff=128, vocab_size=200, scan_layers=False, **kw)
    k1, k2 = jax.random.split(rng)
    params = jax.tree.map(  # move norms and biases off their init values
        lambda a: a + 0.1 * jax.random.normal(k1, a.shape, a.dtype),
        T.init(cfg, rng))
    toks = jax.random.randint(k2, (2, 12), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = T.forward(cfg, params, toks)
    got = reference.dense_lm_logits(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
