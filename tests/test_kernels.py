"""Per-kernel validation: sweep shapes/dtypes, assert allclose vs the
pure-jnp oracle (interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref as R
from repro.kernels.bank_matmul import VMEM_BUDGET, _pick_blocks, bank_matmul
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.page_gather import page_gather
from repro.kernels.rg_lru import rg_lru_scan

TOL = dict(rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,Hq,Hkv,D,bq,bk", [
    (128, 4, 4, 64, 64, 64),    # MHA
    (256, 8, 2, 64, 128, 64),   # GQA 4:1
    (128, 4, 1, 128, 32, 128),  # MQA, wide head
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32), (False, None)])
def test_flash_attention_sweep(dtype, S, Hq, Hkv, D, bq, bk, causal, window, rng):
    ks = jax.random.split(rng, 3)
    B = 2
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Smax,Hq,Hkv,D,bk", [
    (256, 8, 2, 64, 128), (512, 4, 4, 128, 256), (128, 8, 1, 64, 64),
])
def test_decode_attention_sweep(dtype, Smax, Hq, Hkv, D, bk, rng):
    ks = jax.random.split(rng, 3)
    B = 3
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, D), dtype)
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, D), dtype)
    lengths = jnp.array([1, Smax // 3, Smax], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, block_k=bk, interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, lengths)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


@pytest.mark.parametrize("S,d,chunk,bd", [(64, 128, 16, 128), (128, 256, 64, 128),
                                          (32, 128, 32, 128)])
def test_rg_lru_sweep(S, d, chunk, bd, rng):
    ks = jax.random.split(rng, 3)
    B = 2
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, d)))
    b = jax.random.normal(ks[1], (B, S, d))
    h0 = jax.random.normal(ks[2], (B, d))
    y, hl = rg_lru_scan(a, b, h0, chunk=chunk, block_d=bd, interpret=True)
    yr, hlr = R.rg_lru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **TOL)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), **TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,di,n,chunk,bdi", [(64, 128, 16, 16, 128),
                                              (32, 256, 8, 32, 128),
                                              (48, 128, 16, 48, 128)])
def test_mamba_scan_sweep(dtype, S, di, n, chunk, bdi, rng):
    ks = jax.random.split(rng, 5)
    B = 2
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di), dtype))
    dtx = jax.random.normal(ks[1], (B, S, di), dtype)
    Bm = jax.random.normal(ks[2], (B, S, n), dtype)
    Cm = jax.random.normal(ks[3], (B, S, n), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.5)
    h0 = jnp.zeros((B, di, n))
    y, hl = mamba_scan(dt, dtx, Bm, Cm, A, h0, chunk=chunk, block_di=bdi,
                       interpret=True)
    yr, hlr = R.mamba_scan_ref(dt, dtx, Bm, Cm, A, h0)
    tol = (dict(rtol=1e-2, atol=1e-2) if dtype == jnp.float32
           else dict(rtol=5e-2, atol=5e-2))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(hl, np.float32),
                               np.asarray(hlr, np.float32), **tol)


# ---------------------------------------------------------------------------
# Model-shaped scan sweeps through the ops dispatch seam (ISSUE 10): the
# shapes the promoted ssm/griffin serving paths actually emit, including
# ragged sequence lengths that exercise the identity-padded tail chunk
# (dt=0 / a=1, b=0 pads are exact no-ops for the recurrences), in BOTH the
# ref oracle and interpret mode.  In ref mode the padded-then-sliced result
# must be bitwise the unpadded oracle — that is the serving contract
# benchmarks/mixed_zoo.py gates on.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("B,S,di,n,chunk", [
    (2, 13, 64, 8, 16),   # ssm adapter tiny config, ragged S
    (1, 16, 64, 8, 16),   # exact chunk multiple
    (2, 40, 128, 8, 32),  # two full chunks + ragged tail
])
def test_mamba_scan_model_shaped_modes(mode, B, S, di, n, chunk, rng):
    ks = jax.random.split(rng, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di)))
    dtx = jax.random.normal(ks[1], (B, S, di))
    Bm = jax.random.normal(ks[2], (B, S, n))
    Cm = jax.random.normal(ks[3], (B, S, n))
    A = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.5)
    h0 = jnp.zeros((B, di, n))
    # identity-pad exactly as ssm._run_scan does before dispatching
    pad = (-S) % chunk
    args = [dt, dtx, Bm, Cm]
    if pad:
        args = [jnp.pad(a, [(0, 0), (0, pad), (0, 0)]) for a in args]
    y, hl = ops.mamba_scan(*args, A, h0, chunk=chunk,
                           block_di=min(512, di), mode=mode)
    y = y[:, :S]
    yr, hlr = R.mamba_scan_ref(dt, dtx, Bm, Cm, A, h0)
    if mode == "ref":
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
        np.testing.assert_array_equal(np.asarray(hl), np.asarray(hlr))
    else:
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr),
                                   rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("B,S,d,chunk", [
    (2, 13, 32, 16),   # griffin adapter tiny config, ragged S
    (1, 16, 32, 16),   # exact chunk multiple
    (2, 40, 128, 32),  # two full chunks + ragged tail
])
def test_rg_lru_model_shaped_modes(mode, B, S, d, chunk, rng):
    ks = jax.random.split(rng, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, d)))
    b = jax.random.normal(ks[1], (B, S, d))
    h0 = jax.random.normal(ks[2], (B, d))
    # identity-pad exactly as griffin._run_scan_diag does (a=1, b=0)
    pad = (-S) % chunk
    ap, bp = a, b
    if pad:
        ap = jnp.pad(a, [(0, 0), (0, pad), (0, 0)], constant_values=1.0)
        bp = jnp.pad(b, [(0, 0), (0, pad), (0, 0)])
    y, hl = ops.rg_lru_scan(ap, bp, h0, chunk=chunk,
                            block_d=min(512, d), mode=mode)
    y = y[:, :S]
    yr, hlr = R.rg_lru_ref(a, b, h0)
    if mode == "ref":
        np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
        np.testing.assert_array_equal(np.asarray(hl), np.asarray(hlr))
    else:
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **TOL)
        np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), **TOL)


def test_dispatch_counters_track_trace_time_dispatches(rng):
    """The dead-kernel observable: dispatch counts increment per traced op
    (benchmarks/mixed_zoo.py gates mamba_scan/rg_lru_scan > 0 on it)."""
    ops.reset_dispatch_counts()
    assert ops.dispatch_counts() == {}
    ks = jax.random.split(rng, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 16, 32)))
    b = jax.random.normal(ks[1], (1, 16, 32))
    h0 = jnp.zeros((1, 32))
    ops.rg_lru_scan(a, b, h0, mode="ref")
    ops.rg_lru_scan(a, b, h0, mode="ref")
    counts = ops.dispatch_counts()
    assert counts.get("rg_lru_scan") == 2
    assert "mamba_scan" not in counts
    ops.reset_dispatch_counts()
    assert ops.dispatch_counts() == {}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,M,K,F,bm,bk,bf", [
    (3, 8, 32, 64, 8, 32, 64),      # serving-head scale, single block
    (2, 16, 64, 128, 8, 32, 128),   # multi-block m and k
    (4, 8, 128, 96, 8, 64, 32),     # multi-block k and f
    (3, 16, 64, 256, None, None, None),  # blocks picked from the shapes
    (2, 13, 64, 256, None, None, None),  # ragged M, one whole-M block
    (2, 13, 64, 256, 8, 32, 128),   # ragged M padded to a block multiple
])
@pytest.mark.parametrize("broadcast_x", [False, True])
@pytest.mark.parametrize("bias", [False, True])
def test_bank_matmul_sweep(dtype, N, M, K, F, bm, bk, bf, broadcast_x, bias, rng):
    ks = jax.random.split(rng, 3)
    x = jax.random.normal(ks[0], (M, K) if broadcast_x else (N, M, K), dtype)
    w = jax.random.normal(ks[1], (N, K, F), dtype)
    b = jax.random.normal(ks[2], (N, F), dtype) if bias else None
    out = bank_matmul(x, w, b, block_m=bm, block_k=bk, block_f=bf,
                      interpret=True)
    ref = R.bank_matmul_ref(x, w, b)
    assert out.shape == (N, M, F) and out.dtype == jnp.float32
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)


@pytest.mark.parametrize("block_k", [None, 64])  # one k block, and split k
@pytest.mark.parametrize("bias", [False, True])
def test_bank_matmul_bf16_operands_match_f32_upcast(block_k, bias, rng):
    """bf16 tiles go to the dot as they are: bf16 x bf16 products are exact
    in f32, so the result is the f32 contraction of the upcast operands up
    to f32 summation order."""
    ks = jax.random.split(rng, 3)
    x = jax.random.normal(ks[0], (3, 24, 256), jnp.bfloat16)
    w = jax.random.normal(ks[1], (3, 256, 384), jnp.bfloat16)
    b = jax.random.normal(ks[2], (3, 384), jnp.bfloat16) if bias else None
    out = bank_matmul(x, w, b, block_k=block_k, interpret=True)
    up = lambda a: None if a is None else a.astype(jnp.float32)
    ref = R.bank_matmul_ref(up(x), up(w), up(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def _grid_steps(N, M, K, F, blk):
    return (N * (blk.rows // blk.block_m) * (F // blk.block_f)
            * (K // blk.block_k))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_bank_block_rule_at_the_zoo_head(dtype):
    """StableLM-2-1.6B's four heads (M 4 x 128, K 2048, F 100352): one m
    and one k block, a few hundred steps, and a VMEM estimate inside both
    the budget and the limit passed to the compiler, in bf16 and f32."""
    N, M, K, F = 4, 512, 2048, 100352
    blk = _pick_blocks(M, K, F, dtype, dtype)
    assert (blk.block_m, blk.rows, blk.block_k) == (M, M, K)
    assert F % blk.block_f == 0 and blk.block_f % 128 == 0
    assert _grid_steps(N, M, K, F, blk) <= 1024
    assert blk.vmem_bytes <= VMEM_BUDGET
    assert blk.vmem_bytes < blk.vmem_limit


@pytest.mark.parametrize("M,most_rows", [
    (520, 528),     # ragged: no padding past the next multiple of 16
    (4, 16),        # a decode batch: one whole block
    (5000, 5000 + 5 * 16),  # above MAX_BLOCK_M: equal blocks, a sublane each
])
def test_bank_block_rule_pads_at_most_a_sublane_a_block(M, most_rows):
    blk = _pick_blocks(M, 2048, 100352, jnp.bfloat16, jnp.bfloat16,
                      jnp.bfloat16)
    assert M <= blk.rows <= most_rows and blk.rows % blk.block_m == 0
    assert blk.block_m == M or blk.block_m % 16 == 0
    assert blk.vmem_bytes <= VMEM_BUDGET and blk.vmem_bytes < blk.vmem_limit


@pytest.mark.parametrize("N,M,K,F", [(3, 8, 32, 64), (4, 8, 128, 96),
                                     (2, 16, 64, 128)])
def test_bank_block_rule_takes_whole_small_dims(N, M, K, F):
    blk = _pick_blocks(M, K, F, jnp.float32, jnp.float32, jnp.float32)
    assert (blk.block_m, blk.block_k, blk.block_f, blk.rows) == (M, K, F, M)
    assert _grid_steps(N, M, K, F, blk) == N


def test_bank_block_rule_splits_k_only_when_it_cannot_fit():
    """A contraction too wide for the budget gets the accumulating path,
    with a lane-aligned k block that divides K."""
    K = 65536
    blk = _pick_blocks(512, K, 100352, jnp.float32, jnp.float32)
    assert blk.block_k < K and K % blk.block_k == 0 and blk.block_k % 128 == 0
    assert blk.vmem_bytes <= VMEM_BUDGET and blk.vmem_bytes < blk.vmem_limit


def test_bank_matmul_ref_is_bitwise_per_member(rng):
    """The ref oracle IS the per-member contraction: bitwise equal to
    running each member's einsum separately (the engine's ref-mode serving
    parity contract, DESIGN.md S2)."""
    ks = jax.random.split(rng, 2)
    x = jax.random.normal(ks[0], (8, 32))
    w = jax.random.normal(ks[1], (3, 32, 64))
    out = jax.jit(R.bank_matmul_ref)(x, w)
    for i in range(3):
        per = jax.jit(lambda xx, ww: jnp.einsum(
            "mk,kf->mf", xx, ww, preferred_element_type=jnp.float32))(x, w[i])
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(per))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("P,page,N", [(32, 128, 8), (64, 256, 64), (8, 512, 3)])
def test_page_gather_sweep(dtype, P, page, N, rng):
    if dtype == jnp.int32:
        pool = jax.random.randint(rng, (P, page), -1000, 1000, dtype)
    else:
        pool = jax.random.normal(rng, (P, page), dtype)
    table = jax.random.randint(rng, (N,), 0, P)
    out = page_gather(pool, table, interpret=True)
    assert out.dtype == pool.dtype  # a gather is a copy: dtype preserved
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(R.page_gather_ref(pool, table)))


# ---------------------------------------------------------------------------
# decode_attention edge cases (DESIGN.md D1): the decode hot path feeds this
# kernel fresh-admitted rows (length 0 after the bump convention), ragged
# lengths that never align to block_k, every GQA ratio the zoo uses, and
# bf16 caches — each must match the jnp oracle (f32 accumulation) exactly
# where the contract is exact and within bf16 tolerance elsewhere.
# ---------------------------------------------------------------------------


def test_decode_attention_length_zero_is_exact_zeros(rng):
    """A fully-masked row (length 0) must emit EXACT zeros from both the
    kernel body and the ref oracle — not NaN from a 0/0 softmax.  The paged
    decoder relies on this: padding rows replicate a real row's table but
    their outputs are discarded, and the guarantee that garbage contributes
    nothing is what makes paged == unpaged bitwise."""
    ks = jax.random.split(rng, 3)
    B, Smax, Hq, Hkv, D = 3, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, D))
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, D))
    lengths = jnp.array([0, 7, 0], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    np.testing.assert_array_equal(np.asarray(ref[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(ref[2]), 0.0)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]), **TOL)


@pytest.mark.parametrize("lengths", [[1, 37, 129], [63, 64, 65], [255, 2, 130]])
def test_decode_attention_ragged_lengths_vs_block_k(lengths, rng):
    """Lengths that straddle block_k boundaries (the common case — decode
    lengths grow by one per step and are never block-aligned)."""
    ks = jax.random.split(rng, 3)
    B, Smax, Hq, Hkv, D = 3, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, D))
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, D))
    lens = jnp.array(lengths, jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_k=64, interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2), (8, 1)])
def test_decode_attention_gqa_group_sizes(Hq, Hkv, rng):
    """GQA group sizes 1 (MHA), 4, and 8 (MQA) — the head-replication
    indexing inside the kernel vs the oracle's repeat."""
    ks = jax.random.split(rng, 3)
    B, Smax, D = 2, 128, 64
    q = jax.random.normal(ks[0], (B, Hq, D))
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, D))
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, D))
    lengths = jnp.array([5, 128], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_decode_attention_bf16_accumulates_f32(rng):
    """bf16 q/k/v: the kernel accumulates in f32, so it must track the
    all-f32 oracle within bf16 input-rounding error — far tighter than a
    bf16-accumulated softmax-weighted sum would manage."""
    ks = jax.random.split(rng, 3)
    B, Smax, Hq, Hkv, D = 2, 256, 8, 2, 64
    qf = jax.random.normal(ks[0], (B, Hq, D))
    kf = jax.random.normal(ks[1], (B, Smax, Hkv, D))
    vf = jax.random.normal(ks[2], (B, Smax, Hkv, D))
    lengths = jnp.array([100, 256], jnp.int32)
    out = decode_attention(qf.astype(jnp.bfloat16), kf.astype(jnp.bfloat16),
                           vf.astype(jnp.bfloat16), lengths, interpret=True)
    ref = R.decode_attention_ref(qf.astype(jnp.bfloat16).astype(jnp.float32),
                                 kf.astype(jnp.bfloat16).astype(jnp.float32),
                                 vf.astype(jnp.bfloat16).astype(jnp.float32),
                                 lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_page_gather_permutation_roundtrip(rng):
    """Gathering a full permutation reproduces the pool rows exactly in
    permuted order, and the pool itself is untouched (gather is a copy)."""
    P, page = 16, 64
    pool = jax.random.normal(rng, (P, page))
    pool_before = np.asarray(pool).copy()
    perm = np.random.default_rng(0).permutation(P)
    out = page_gather(pool, jnp.asarray(perm), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), pool_before[perm])
    np.testing.assert_array_equal(np.asarray(pool), pool_before)


def test_page_gather_duplicate_pages(rng):
    """The same physical page referenced from several table slots (padding
    rows in the decoder replicate a live row's table): every duplicate slot
    must read back the identical bytes."""
    P, page = 8, 32
    pool = jax.random.normal(rng, (P, page))
    table = jnp.array([3, 3, 0, 7, 3, 0], jnp.int32)
    out = np.asarray(page_gather(pool, table, interpret=True))
    np.testing.assert_array_equal(out, np.asarray(pool)[np.array(table)])
    np.testing.assert_array_equal(out[0], out[1])
    np.testing.assert_array_equal(out[1], out[4])
    np.testing.assert_array_equal(out[2], out[5])


def test_page_gather_requires_explicit_interpret():
    """Mode is decided ONLY by kernels.ops / REPRO_KERNEL_MODE: the raw
    kernels take `interpret` as a required keyword — no silent default that
    could route a kernel-mode deployment through the interpreter."""
    pool = jnp.zeros((4, 8))
    table = jnp.zeros((2,), jnp.int32)
    with pytest.raises(TypeError):
        page_gather(pool, table)  # noqa: missing required kwarg
    q = jnp.zeros((1, 2, 8))
    kc = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(TypeError):
        decode_attention(q, kc, kc, jnp.zeros((1,), jnp.int32))


# ---------------------------------------------------------------------------
# ops-dispatch mode matrix: the PUBLIC entry points (what the serving hot
# path calls) under the ambient REPRO_KERNEL_MODE must match the pure-jnp
# oracles.  scripts/ci.sh runs these under BOTH CPU-executable modes
# (ref, interpret), so a dispatch-layer regression — wrong kwargs threading,
# a kernel body drifting from its oracle — fails the matrix, not just the
# direct per-kernel sweeps above.
# ---------------------------------------------------------------------------


def _ops_case(op, rng):
    """(args, kwargs, ref_fn) for one small but multi-block instance."""
    ks = jax.random.split(rng, 6)
    if op == "flash_attention":
        q = jax.random.normal(ks[0], (2, 128, 4, 64))
        k = jax.random.normal(ks[1], (2, 128, 2, 64))
        v = jax.random.normal(ks[2], (2, 128, 2, 64))
        return ((q, k, v), dict(causal=True, block_q=64, block_k=64),
                lambda: R.flash_attention_ref(q, k, v, causal=True))
    if op == "decode_attention":
        q = jax.random.normal(ks[0], (3, 8, 64))
        kc = jax.random.normal(ks[1], (3, 256, 2, 64))
        vc = jax.random.normal(ks[2], (3, 256, 2, 64))
        lengths = jnp.array([1, 100, 256], jnp.int32)
        return ((q, kc, vc, lengths), dict(block_k=128),
                lambda: R.decode_attention_ref(q, kc, vc, lengths))
    if op == "mamba_scan":
        dt = jax.nn.softplus(jax.random.normal(ks[0], (2, 32, 128)))
        dtx = jax.random.normal(ks[1], (2, 32, 128))
        Bm = jax.random.normal(ks[2], (2, 32, 8))
        Cm = jax.random.normal(ks[3], (2, 32, 8))
        A = -jnp.exp(jax.random.normal(ks[4], (128, 8)) * 0.5)
        h0 = jnp.zeros((2, 128, 8))
        return ((dt, dtx, Bm, Cm, A, h0), dict(chunk=16, block_di=128),
                lambda: R.mamba_scan_ref(dt, dtx, Bm, Cm, A, h0))
    if op == "rg_lru_scan":
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 64, 128)))
        b = jax.random.normal(ks[1], (2, 64, 128))
        h0 = jax.random.normal(ks[2], (2, 128))
        return ((a, b, h0), dict(chunk=16, block_d=128),
                lambda: R.rg_lru_ref(a, b, h0))
    if op == "page_gather":
        pool = jax.random.normal(ks[0], (32, 256))
        table = jax.random.randint(ks[1], (16,), 0, 32)
        return ((pool, table), {},
                lambda: R.page_gather_ref(pool, table))
    if op == "bank_matmul":
        x = jax.random.normal(ks[0], (8, 64))
        w = jax.random.normal(ks[1], (3, 64, 96))
        b = jax.random.normal(ks[2], (3, 96))
        return ((x, w, b), dict(block_m=8, block_k=32, block_f=32),
                lambda: R.bank_matmul_ref(x, w, b))
    raise ValueError(op)


# the mode matrix is driven by the machine-readable dispatch table, so an op
# added to kernels/ops.py without an OP_TABLE entry (or an _ops_case) fails
# here, and the contract checker (repro.analysis.contracts) proves the same
# table abstractly in CI before this numeric sweep runs
OPS = sorted(ops.OP_TABLE)


def test_op_table_is_the_public_surface():
    """Every OP_TABLE row points at this module's public dispatcher and a
    real ref oracle; the roles are distinct callables."""
    for name, spec in ops.OP_TABLE.items():
        assert spec.name == name
        assert getattr(ops, name) is spec.dispatch
        assert spec.ref is getattr(R, spec.ref.__name__)
        assert spec.kernel is not spec.ref is not spec.dispatch


@pytest.mark.parametrize("op", OPS)
def test_ops_mode_matrix_matches_oracle(op, rng):
    mode = ops.default_mode()
    if mode == "kernel":
        pytest.skip("TPU kernel mode not exercisable on this host")
    args, kw, ref_fn = _ops_case(op, rng)
    out = ops.OP_TABLE[op].dispatch(*args, **kw)
    ref = ref_fn()
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=1e-2, atol=1e-2)


def test_ops_default_mode_env_override(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    assert ops.default_mode() == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    assert ops.default_mode() == "ref"
    monkeypatch.delenv("REPRO_KERNEL_MODE")
    assert ops.default_mode() in ("ref", "kernel")  # host-resolved


def test_model_uses_kernel_equivalence(rng):
    """ops.flash_attention(mode=interpret) == the model's jnp attention."""
    from repro.kernels import ops
    from repro.models import layers as L

    B, S, Hq, Hkv, D = 2, 128, 4, 2, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    model_attn = L.gqa_attention(q, k, v, L.attention_mask(pos, pos, True, None))
    kern = ops.flash_attention(q, k, v, causal=True, mode="interpret",
                               block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(model_attn),
                               rtol=1e-4, atol=1e-4)
