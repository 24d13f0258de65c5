"""GQA decode attention against a (possibly partially filled) KV cache.

One new token per sequence: q (B, Hq, D) vs cache (B, Smax, Hkv, D) with a
per-row valid length.  Grid (B, Hkv, num_kv_blocks): the G = Hq/Hkv query
heads of one kv head are processed together as the MXU M-dimension; the kv
axis is the sequential innermost axis carrying online-softmax state in VMEM.

VMEM per instance: q (G, D) + k,v (block_k, D) + acc (G, D) + m/l — tiny;
block_k = 256 keeps the HBM reads wide.  Length masking is positional
(no gather): a block whose start >= length is skipped entirely.

The wrapper hands the kernel a head-major (B, Hkv, Smax, D) view of the
cache and writes a (B, Hkv, G, D) output, so every block's last two dims
are (block_k, D) or (G, D): the TPU lowering needs them divisible by
(8, 128) or whole, which per-head blocks over the (H, D) minor dims of the
cache layout are not (and G is 1 for MHA).  A cache longer than a block
but not a multiple of it is zero-padded; the length mask hides the pad.
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


def _decode_kernel(
    len_ref,  # scalar-prefetch: (B,) lengths
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, block_k: int, scale: float, grp: int, num_kv_blocks: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    length = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bk)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kp < length, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, 0] = m_cur

    @pl.when(ki == num_kv_blocks - 1)
    def _emit():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_k", "interpret", "scale")
)
def decode_attention(
    q: jax.Array,  # (B, Hq, D)
    k_cache: jax.Array,  # (B, Smax, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) int32
    scale: Optional[float] = None,
    block_k: int = 256,
    *,
    interpret: bool,
) -> jax.Array:
    B, Hq, D = q.shape
    Smax, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert Hq % Hkv == 0
    grp = Hq // Hkv
    block_k = min(block_k, Smax)
    Sp = -(-Smax // block_k) * block_k
    nk = Sp // block_k
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)

    def head_major(x):  # (B, Smax, Hkv, D) -> (B, Hkv, Sp, D)
        x = x.transpose(0, 2, 1, 3)
        return (jnp.pad(x, [(0, 0), (0, 0), (0, Sp - Smax), (0, 0)])
                if Sp > Smax else x)

    qg = q.reshape(B, Hkv, grp, D)
    kernel = functools.partial(
        _decode_kernel, block_k=block_k, scale=scale, grp=grp, num_kv_blocks=nk
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, grp, D), lambda b, h, ki, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, lens: (b, h, ki, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, lens: (b, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, grp, D), lambda b, h, ki, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((grp, D), jnp.float32),
                pltpu.VMEM((grp, 128), jnp.float32),
                pltpu.VMEM((grp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, grp, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, head_major(k_cache), head_major(v_cache))
    return out.reshape(B, Hq, D)
