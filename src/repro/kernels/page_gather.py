"""Paged weight assembly — the TPU analogue of GEMEL's partial swap.

Merged workloads keep weights in a paged HBM pool: shared layers' pages are
resident once; switching the active model assembles its contiguous parameter
buffer by gathering its page list (private pages freshly DMA'd, shared pages
reused in place).  ``page_gather`` is that assembly step: out[i] =
pool[page_table[i]].

TPU-idiomatic implementation: the page table is a *scalar-prefetch* operand
(pltpu.PrefetchScalarGridSpec) so the index arrives before the grid step and
the BlockSpec ``index_map`` itself selects the pool row — the gather becomes
pure block DMA, no vector compute at all, exactly like paged-attention KV
lookups.  Grid (N,); VMEM per step = one page.

Each page travels as a (rows, lanes) tile — (page/128, 128) when the page
is lane-aligned, else (1, page) — so a block's last two dims are the whole
page: the TPU lowering refuses a (1, page) block over a (P, page) pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(table_ref, pool_ref, out_ref):
    # pool block was already selected via index_map; plain copy.
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather(
    pool: jax.Array,  # (P, page)
    page_table: jax.Array,  # (N,) int32
    *,
    interpret: bool,
) -> jax.Array:
    """Returns out (N, page) with out[i] = pool[page_table[i]]."""
    P, page = pool.shape
    (N,) = page_table.shape
    tile = (page // 128, 128) if page % 128 == 0 else (1, page)

    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N,),
            in_specs=[
                pl.BlockSpec((1, *tile), lambda i, table: (table[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, *tile), lambda i, table: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, *tile), pool.dtype),
        interpret=interpret,
        name="page_gather",
    )(page_table.astype(jnp.int32), pool.reshape(P, *tile))
    return out.reshape(N, page)
