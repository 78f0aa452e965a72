"""Block-gather pack kernel -- the transport serialization hot path.

The M->N redistribution planner (repro.core.redistribute) reduces every
producer->consumer exchange to "gather these row-blocks of a 2-D buffer into
one contiguous send buffer".  On TPU the natural implementation is an
index-map-driven DMA: the block offsets arrive as a *scalar-prefetch* operand
(pltpu.PrefetchScalarGridSpec), the grid walks output tiles, and each tile's
``index_map`` points the DMA engine at the right source row -- no gather
scatter ops, just strided HBM->VMEM->HBM copies.

Two tile layouts cover the planner's 1-D decompositions of a 2-D buffer:

* ``pack_blocks`` -- row-slab gathers (axis-0 decompositions): tiles are
  (tile_rows, cols) and the scalar operand indexes source row-tiles.
* ``pack_cols``   -- column-slab gathers (axis-1 decompositions): tiles are
  (rows, tile_cols) and the scalar operand indexes source column-tiles, so
  axis!=0 reshards stay on the kernel path instead of falling back to numpy.

A second grid axis walks the other dimension in blocks of ``block_cols`` /
``block_rows``, so a block's bytes do not grow with the field: a whole
(8, 262144) f32 row tile is 8 MiB and overflows the v5e's scoped VMEM once
in, out and their double buffers are counted.  ``choose_block`` and
``sublanes`` give the sizes the Mosaic compiler accepts; ``BLOCK_BYTES``
bounds each block.  The planner pads ragged blocks up to tile granularity
(LowFive ships whole hyperslabs, same idea).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# One block's bytes: in + out, each double-buffered, is 4 blocks = 8 MiB,
# half of the v5e's 16 MiB default scoped VMEM (a 4 MiB block compiled
# there, an 8 MiB one ran out of VMEM).
BLOCK_BYTES = 2 << 20


def sublanes(dtype) -> int:
    """Row granule of a block: 8 sublanes of 32 bits, more rows when packed."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def choose_block(extent: int, granule: int, unit_bytes: int,
                 budget: int) -> int:
    """Block length along a dimension of ``extent`` indices, each moving
    ``unit_bytes``: the whole dimension when it fits ``budget`` or is no
    longer than ``granule``, else the largest multiple of ``granule`` that
    fits and divides ``extent``, else that multiple with a ragged last
    block."""
    if extent * unit_bytes <= budget or extent <= granule:
        return extent
    cap = max(granule, budget // unit_bytes // granule * granule)
    for b in range(cap, 0, -granule):
        if extent % b == 0:
            return b
    return cap


def _pack_kernel(offs_ref, src_ref, out_ref):
    out_ref[...] = src_ref[...]


def pack_blocks(
    src: jnp.ndarray,          # (R, C) source buffer
    tile_offsets: jnp.ndarray,  # (T,) int32: source row-tile index per out tile
    tile_rows: int,
    block_cols: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather T row-tiles of ``tile_rows`` rows each into a contiguous buffer.

    out[t*tile_rows:(t+1)*tile_rows] = src[tile_offsets[t]*tile_rows : ...]

    Each tile moves in ``block_cols``-wide blocks.
    A ragged source (rows not a multiple of ``tile_rows``) is zero-padded up
    to tile granularity so the last tile's DMA stays in bounds; callers that
    gather the tail tile (the redistribution pack executor) trim the pad rows
    back off the packed output.
    """
    r, c = src.shape
    pad = -r % tile_rows
    if pad:
        src = jnp.pad(src, ((0, pad), (0, 0)))
        r += pad
    t = tile_offsets.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, pl.cdiv(c, block_cols)),
        in_specs=[
            pl.BlockSpec((tile_rows, block_cols),
                         lambda i, j, offs: (offs[i], j)),
        ],
        out_specs=pl.BlockSpec((tile_rows, block_cols),
                               lambda i, j, offs: (i, j)),
    )
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t * tile_rows, c), src.dtype),
        interpret=interpret,
    )(tile_offsets, src)


def pack_cols(
    src: jnp.ndarray,           # (R, C) source buffer
    tile_offsets: jnp.ndarray,  # (T,) int32: source col-tile index per out tile
    tile_cols: int,
    block_rows: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather T column-tiles of ``tile_cols`` columns each, contiguously.

    out[:, t*tile_cols:(t+1)*tile_cols] = src[:, tile_offsets[t]*tile_cols : ...]

    The column twin of ``pack_blocks``: the grid walks output column tiles
    and the scalar-prefetch operand points each tile's DMA at the right
    source column band, ``block_rows`` rows at a time.  A ragged source
    (columns not a multiple of ``tile_cols``) is zero-padded up to tile
    granularity; callers trim the pad columns back off the packed output.
    On TPU ``tile_cols`` must be a multiple of the 128-lane width or the
    whole (padded) width.
    """
    r, c = src.shape
    pad = -c % tile_cols
    if pad:
        src = jnp.pad(src, ((0, 0), (0, pad)))
        c += pad
    t = tile_offsets.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, pl.cdiv(r, block_rows)),
        in_specs=[
            pl.BlockSpec((block_rows, tile_cols),
                         lambda i, j, offs: (j, offs[i])),
        ],
        out_specs=pl.BlockSpec((block_rows, tile_cols),
                               lambda i, j, offs: (j, i)),
    )
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, t * tile_cols), src.dtype),
        interpret=interpret,
    )(tile_offsets, src)
