"""RG-LRU diagonal linear recurrence scan for TPU.

h_t = a_t * h_{t-1} + b_t over (B, S, d) with per-channel state (B, d).

Grid (B, num_d_blocks, num_chunks): channels are embarrassingly parallel
(blocked to the 128-lane register width x block_d), the chunk axis is the
sequential innermost axis carrying h in VMEM scratch.  Within a chunk the
time loop is a fori_loop over rows of the (chunk, block_d) VMEM tile —
sublane-major traversal, one VPU multiply-add per step.

VMEM per instance: a,b,y tiles (chunk, block_d) x 3 + h (1, block_d).
chunk=256, block_d=512, f32: ~1.6 MB.

The (B, d) initial and final states ride as (B, 1, d) views, so their
blocks' last two dims are (1, block_d): the TPU lowering refuses a
(1, block_d) block over a (B, d) array whenever B > 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rg_lru_kernel(a_ref, b_ref, h0_ref, y_ref, hlast_ref, h_ref, *, chunk: int,
                   num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        at = a_ref[0, t, :].astype(jnp.float32)
        bt = b_ref[0, t, :].astype(jnp.float32)
        h = at * h + bt
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[0, :])
    h_ref[...] = h[None, :]

    @pl.when(ci == num_chunks - 1)
    def _emit():
        hlast_ref[0] = h[None, :].astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rg_lru_scan(
    a: jax.Array,  # (B, S, d)
    b: jax.Array,  # (B, S, d)
    h0: jax.Array,  # (B, d)
    chunk: int = 256,
    block_d: int = 512,
    *,
    interpret: bool,
):
    """Returns (y (B,S,d) float32, h_last (B,d) float32)."""
    B, S, d = a.shape
    chunk = min(chunk, S)
    block_d = min(block_d, d)
    assert S % chunk == 0 and d % block_d == 0
    nc, nd = S // chunk, d // block_d

    kernel = functools.partial(_rg_lru_kernel, chunk=chunk, num_chunks=nc)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, di, ci: (b_, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, di, ci: (b_, ci, di)),
            pl.BlockSpec((1, 1, block_d), lambda b_, di, ci: (b_, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, di, ci: (b_, ci, di)),
            pl.BlockSpec((1, 1, block_d), lambda b_, di, ci: (b_, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, d), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, d))
    return y, h_last.reshape(B, d)
