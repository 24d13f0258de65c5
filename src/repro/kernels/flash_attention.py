"""Blocked flash attention (causal / sliding-window, GQA) for TPU.

Grid: (B, Hq, num_q_blocks, num_kv_blocks) — the last axis is innermost and
executed sequentially on TPU, so the online-softmax state (m, l, acc) lives
in VMEM scratch and carries across kv steps; the output block is emitted at
the final kv step.

The wrapper takes the model's (B, S, H, D) layout and hands the kernel a
head-major (B, H, S, D) view, so every block's last two dims are
(block, D): the TPU lowering needs them divisible by (8, 128) or whole, and
a per-head block over the (H, D) minor dims of the model layout is neither.
A sequence longer than a block but not a multiple of it is zero-padded to
one; padded keys are masked, padded query rows are sliced off.

VMEM working set per program instance:
    q block   (block_q, D)        bf16/f32
    k,v block (block_k, D)  x 2
    acc       (block_q, D)        f32
    m, l      (block_q, 128)      f32 (lane-padded)
With block_q = block_k = 128 and D = 128 this is ~0.5 MB — far under the
~16 MB/core VMEM budget; block sizes are exposed as arguments and swept in
the kernel tests.

Causal + window blocks that are fully masked are skipped via @pl.when on the
block indices (no FLOPs, no VMEM traffic beyond the prefetch).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,  # blocks
    acc_ref, m_ref, l_ref,  # VMEM scratch
    *, block_q: int, block_k: int, scale: float, causal: bool,
    window: Optional[int], num_kv_blocks: int, grp: int, kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level relevance: causal => k_start <= q_end; window => block not
    # entirely older than the window
    relevant = k_start <= q_start + block_q - 1 if causal else True
    if num_kv_blocks * block_k > kv_len:  # padded tail: skip all-pad blocks
        relevant = jnp.logical_and(relevant, k_start < kv_len)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, (q_start - (k_start + block_k - 1)) < window
        )

    @pl.when(relevant if not isinstance(relevant, bool) else relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)

        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= (qp - kp) < window
        if num_kv_blocks * block_k > kv_len:
            mask &= kp < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]  # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_cur

    @pl.when(ki == num_kv_blocks - 1)
    def _emit():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret", "scale"),
)
def flash_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0
    grp = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    step = int(np.lcm(block_q, block_k))
    Sp = -(-S // step) * step
    nq, nk = Sp // block_q, Sp // block_k
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)

    def head_major(x):  # (B, S, H, D) -> (B, H, Sp, D)
        x = x.transpose(0, 2, 1, 3)
        return jnp.pad(x, [(0, 0), (0, 0), (0, Sp - S), (0, 0)]) if Sp > S else x

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, window=window, num_kv_blocks=nk, grp=grp, kv_len=S,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // grp, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // grp, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # m (lane-padded)
            pltpu.VMEM((block_q, 128), jnp.float32),  # l (lane-padded)
        ],
        interpret=interpret,
        name="flash_attention",
    )(head_major(q), head_major(k), head_major(v))
    return out[:, :, :S].transpose(0, 2, 1, 3)
