"""Suffix-bank grouped GEMM — the fused fan-out of a merged group's heads.

GEMEL serving shares one trunk across a merged group but still owes every
member its private suffix; dispatching those suffixes one by one is pure
launch tax (DESIGN.md S2).  This kernel executes the whole fan-out in ONE
``pallas_call``:

    out[n] = x[n] @ w[n] (+ b[n])        n = 0..N-1 bank members

with ``x`` either banked ``(N, M, K)`` (each member consumes its own
activations, e.g. the second FC of a head) or broadcast ``(M, K)`` (every
member consumes the same shared trunk features — the common first-layer
case, where the feature block is fetched into VMEM once per (m, k) tile and
reused across the bank axis via the index map).

Grid: (N, num_m_blocks, num_f_blocks, num_k_blocks) — k innermost and
sequential on TPU, so the f32 accumulator lives in VMEM scratch across k
steps and the output tile is emitted at the final k step.  VMEM working set
per program instance: x (bm, bk) + w (bk, bf) + acc (bm, bf) f32 — with the
default 128-blocks that is ~0.2 MB, far under the ~16 MB/core budget.

Accumulation is float32 regardless of input dtype (the ``preferred_element_
type`` convention of the model stack); the output is float32 and callers
cast, mirroring ``models.layers.dense``/``unembed``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bank_kernel(x_ref, w_ref, o_ref, acc_ref, *, num_k_blocks: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if x.ndim == 3:  # banked x carries the (1,) bank block axis
        x = x[0]
    acc_ref[...] += jax.lax.dot(
        x.astype(jnp.float32), w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == num_k_blocks - 1)
    def _emit():
        o_ref[0, :, :] = acc_ref[...]


def _bank_bias_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, num_k_blocks: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if x.ndim == 3:
        x = x[0]
    acc_ref[...] += jax.lax.dot(
        x.astype(jnp.float32), w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == num_k_blocks - 1)
    def _emit():
        o_ref[0, :, :] = acc_ref[...] + b_ref[0].astype(jnp.float32)  # (1, bf)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_f", "block_k", "interpret"),
)
def bank_matmul(
    x: jax.Array,  # (N, M, K) banked, or (M, K) broadcast across the bank
    w: jax.Array,  # (N, K, F) stacked private weights
    b: Optional[jax.Array] = None,  # (N, F) stacked biases
    block_m: int = 128,
    block_f: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    """Returns (N, M, F) float32 with out[n] = x[n] @ w[n] (+ b[n]).

    Rows beyond a multiple of ``block_m`` are zero-padded and sliced off, so
    any M = batch * seq the serving path emits is accepted.  The bias rides
    as an (N, 1, F) view: its block's last two dims are then (1, block_f),
    which the TPU lowering accepts where an (N, F) bias's (1, block_f) block
    is refused."""
    N, K, F = w.shape
    broadcast = x.ndim == 2
    M = x.shape[0] if broadcast else x.shape[1]
    assert x.shape[-1] == K, (x.shape, w.shape)
    if not broadcast:
        assert x.shape[0] == N, (x.shape, w.shape)
    block_m = min(block_m, M)
    block_f = min(block_f, F)
    block_k = min(block_k, K)
    assert F % block_f == 0 and K % block_k == 0, (
        (F, K), (block_f, block_k))
    Mp = -(-M // block_m) * block_m
    if Mp > M:
        pad = [(0, Mp - M), (0, 0)] if broadcast else [(0, 0), (0, Mp - M), (0, 0)]
        x = jnp.pad(x, pad)
    nm, nf, nk = Mp // block_m, F // block_f, K // block_k

    if broadcast:
        x_spec = pl.BlockSpec((block_m, block_k), lambda n, mi, fi, ki: (mi, ki))
    else:
        x_spec = pl.BlockSpec((1, block_m, block_k),
                              lambda n, mi, fi, ki: (n, mi, ki))
    w_spec = pl.BlockSpec((1, block_k, block_f), lambda n, mi, fi, ki: (n, ki, fi))
    out_spec = pl.BlockSpec((1, block_m, block_f), lambda n, mi, fi, ki: (n, mi, fi))

    if b is None:
        kernel = functools.partial(_bank_kernel, num_k_blocks=nk)
        in_specs = [x_spec, w_spec]
        operands = (x, w)
    else:
        assert b.shape == (N, F), (b.shape, (N, F))
        kernel = functools.partial(_bank_bias_kernel, num_k_blocks=nk)
        in_specs = [x_spec, w_spec,
                    pl.BlockSpec((1, 1, block_f), lambda n, mi, fi, ki: (n, 0, fi))]
        operands = (x, w, b.reshape(N, 1, F))

    out = pl.pallas_call(
        kernel,
        grid=(N, nm, nf, nk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((N, Mp, F), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32)],
        interpret=interpret,
        name="bank_matmul",
    )(*operands)
    return out[:, :M] if Mp > M else out
