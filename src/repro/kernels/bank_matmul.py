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

Grid: (N, num_m_blocks, num_f_blocks, num_k_blocks), k innermost.  Blocks
follow the call's shapes (:func:`_pick_blocks`) against a VMEM budget of
``VMEM_BUDGET`` bytes for the double-buffered x, w, bias and output tiles
plus the f32 product tile (and the accumulator, where k is split):

* ``block_m`` is the whole M up to ``MAX_BLOCK_M`` rows (a whole-dimension
  block is legal at any size, so ragged M costs no padding); larger M is
  cut into equal blocks of the sublane multiple (16 rows for bf16, 8 for
  f32), padding at most a few sublanes.
* ``block_k`` is the whole K whenever it fits: then there is no k axis, no
  accumulator scratch, and each product tile is written straight to the
  output.  Only a K too large for the budget takes the accumulating path.
* ``block_f`` is the largest lane-aligned divisor of F (F itself, or a
  multiple of 128) that fits what is left.

At the StableLM-2-1.6B head (M 512, K 2048, F 100352, bf16) that is one m
and one k block, so x is fetched once per member, the weights and the
output cross HBM exactly once, and the grid has 196 steps of ~22 us of MXU
work each (``block_f`` 2048).  The scoped-VMEM limit passed to the compiler
covers the estimate.

Operands reach the MXU in their own dtype: bf16 x bf16 products are exact
in f32, so the dot casts only where x and w differ, to the wider of the
two.  Accumulation and the output are float32 regardless of input dtype
(the ``preferred_element_type`` convention of the model stack); callers
cast, mirroring ``models.layers.dense``/``unembed``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MiB = 1 << 20
# Of a TPU core's VMEM, for one call's tiles.  On a TPU v5e the zoo head
# (module docstring) takes 4.37 ms a call from block_f 1792 up, 4.40 at
# 896-1024 and 4.55 at 512; 32 MiB picks 2048.
VMEM_BUDGET = 32 * _MiB
MAX_BLOCK_M = 1024  # rows of x and out held at once
_SCOPED_VMEM_DEFAULT = 16 * _MiB  # the compiler's own limit, never lowered
_VMEM_HEADROOM = 4 * _MiB  # Mosaic's internal scratch beyond the tiles
_LANE = 128


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _sublanes(dtype) -> int:
    """Rows of one (sublane, 128) VMEM tile: 8 for 32-bit, 16 for bf16."""
    return 32 // jnp.dtype(dtype).itemsize


def _lane_blocks(dim: int) -> list:
    """Legal lane-axis blocks of ``dim``, largest first: the whole
    dimension, then every multiple of 128 that divides it."""
    return [dim] + [b for b in range(dim - dim % _LANE, 0, -_LANE)
                    if b != dim and dim % b == 0]


class Blocks(NamedTuple):
    block_m: int
    block_k: int
    block_f: int
    rows: int  # M padded to a multiple of block_m
    vmem_bytes: int  # estimated VMEM working set
    vmem_limit: int  # scoped-VMEM limit passed to the compiler


def _vmem_bytes(block_m: int, block_k: int, block_f: int, num_k_blocks: int,
                x_dtype, w_dtype, b_dtype=None) -> int:
    """Double-buffered x, w, bias and f32 output tiles, plus the f32
    product tile and, where k is split, the accumulator."""
    tiles = (block_m * block_k * jnp.dtype(x_dtype).itemsize
             + block_k * block_f * jnp.dtype(w_dtype).itemsize
             + block_m * block_f * 4)
    if b_dtype is not None:  # a (1, block_f) row fills a whole sublane tile
        tiles += _sublanes(b_dtype) * block_f * jnp.dtype(b_dtype).itemsize
    product = block_m * block_f * 4 * (1 if num_k_blocks == 1 else 2)
    return 2 * tiles + product


def _pick_blocks(M: int, K: int, F: int, x_dtype, w_dtype, b_dtype=None, *,
                 block_m: Optional[int] = None, block_k: Optional[int] = None,
                 block_f: Optional[int] = None) -> Blocks:
    """Blocks for one call from its shapes and dtypes (module docstring);
    an explicit block is clipped to its dimension and kept."""
    if block_m is not None:
        bm = min(block_m, M)
    elif M <= MAX_BLOCK_M:
        bm = M
    else:
        sub = _sublanes(x_dtype)
        bm = _cdiv(_cdiv(M, _cdiv(M, MAX_BLOCK_M)), sub) * sub
    rows = _cdiv(M, bm) * bm

    def est(bk, bf):
        return _vmem_bytes(bm, bk, bf, K // bk, x_dtype, w_dtype, b_dtype)

    def largest(cands, need):  # the first that fits, else the smallest
        return next((c for c in cands if need(c) <= VMEM_BUDGET), cands[-1])

    f_cands = _lane_blocks(F)
    bk = (min(block_k, K) if block_k is not None else
          largest(_lane_blocks(K), lambda c: est(c, f_cands[-1])))
    bf = (min(block_f, F) if block_f is not None else
          largest(f_cands, lambda c: est(bk, c)))
    need = est(bk, bf)
    return Blocks(bm, bk, bf, rows, need,
                  max(_SCOPED_VMEM_DEFAULT, need + _VMEM_HEADROOM))


def _bank_kernel(x_ref, w_ref, *rest, has_bias: bool, num_k_blocks: int):
    b_ref = rest[0] if has_bias else None
    o_ref = rest[1] if has_bias else rest[0]
    x = x_ref[...]
    if x.ndim == 3:  # banked x carries the (1,) bank block axis
        x = x[0]
    w = w_ref[0]
    dt = jnp.promote_types(x.dtype, w.dtype)
    prod = jax.lax.dot(x.astype(dt), w.astype(dt),
                       preferred_element_type=jnp.float32)

    def emit(acc):
        if has_bias:
            acc = acc + b_ref[0].astype(jnp.float32)  # (1, bf)
        o_ref[0, :, :] = acc

    if num_k_blocks == 1:
        emit(prod)
        return

    acc_ref = rest[-1]
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += prod

    @pl.when(ki == num_k_blocks - 1)
    def _emit():
        emit(acc_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_f", "block_k", "interpret"),
)
def bank_matmul(
    x: jax.Array,  # (N, M, K) banked, or (M, K) broadcast across the bank
    w: jax.Array,  # (N, K, F) stacked private weights
    b: Optional[jax.Array] = None,  # (N, F) stacked biases
    block_m: Optional[int] = None,
    block_f: Optional[int] = None,
    block_k: Optional[int] = None,
    *,
    interpret: bool,
) -> jax.Array:
    """Returns (N, M, F) float32 with out[n] = x[n] @ w[n] (+ b[n]).

    Blocks left ``None`` are chosen by :func:`_pick_blocks`.  Rows beyond a
    multiple of ``block_m`` are zero-padded and sliced off, so any M =
    batch * seq the serving path emits is accepted.  The bias rides as an
    (N, 1, F) view: its block's last two dims are then (1, block_f), which
    the TPU lowering accepts where an (N, F) bias's (1, block_f) block is
    refused."""
    N, K, F = w.shape
    broadcast = x.ndim == 2
    M = x.shape[0] if broadcast else x.shape[1]
    assert x.shape[-1] == K, (x.shape, w.shape)
    if not broadcast:
        assert x.shape[0] == N, (x.shape, w.shape)
    blk = _pick_blocks(M, K, F, x.dtype, w.dtype,
                       None if b is None else b.dtype,
                       block_m=block_m, block_k=block_k, block_f=block_f)
    block_m, block_k, block_f, Mp = (blk.block_m, blk.block_k, blk.block_f,
                                     blk.rows)
    assert F % block_f == 0 and K % block_k == 0, (
        (F, K), (block_f, block_k))
    if Mp > M:
        pad = [(0, Mp - M), (0, 0)] if broadcast else [(0, 0), (0, Mp - M), (0, 0)]
        x = jnp.pad(x, pad)
    nm, nf, nk = Mp // block_m, F // block_f, K // block_k

    if broadcast:
        x_spec = pl.BlockSpec((block_m, block_k), lambda n, mi, fi, ki: (mi, ki))
    else:
        x_spec = pl.BlockSpec((1, block_m, block_k),
                              lambda n, mi, fi, ki: (n, mi, ki))
    in_specs = [x_spec, pl.BlockSpec((1, block_k, block_f),
                                     lambda n, mi, fi, ki: (n, ki, fi))]
    operands = [x, w]
    if b is not None:
        assert b.shape == (N, F), (b.shape, (N, F))
        in_specs.append(pl.BlockSpec((1, 1, block_f),
                                     lambda n, mi, fi, ki: (n, 0, fi)))
        operands.append(b.reshape(N, 1, F))

    out = pl.pallas_call(
        functools.partial(_bank_kernel, has_bias=b is not None,
                          num_k_blocks=nk),
        grid=(N, nm, nf, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_m, block_f),
                               lambda n, mi, fi, ki: (n, mi, fi)),
        out_shape=jax.ShapeDtypeStruct((N, Mp, F), jnp.float32),
        scratch_shapes=([] if nk == 1 else
                        [pltpu.VMEM((block_m, block_f), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=blk.vmem_limit),
        interpret=interpret,
        name="bank_matmul",
    )(*operands)
    return out[:, :M] if Mp > M else out
