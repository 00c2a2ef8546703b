"""Pallas TPU kernel: blocked 3D summed-area table (rank-3 prefix sum).

The 3D extension of :mod:`.sat`: three separable passes — prefix along the
innermost axis, then the middle axis, then the slab axis — each a single
``pl.pallas_call`` whose innermost grid axis advances along the scan
direction while a VMEM scratch carries the running tile-edge sums (TPU
grids execute sequentially, so the carry is well-defined).

Rank-3 grid design:

- Blocks are ``(1, 1, bm, bn)`` slices of a ``(B, n1, n2, n3)`` frame
  stack: the trailing two axes carry the (8, 128)-aligned VREG tiling, the
  slab axis rides the grid.  The first two passes are exactly the 2D
  kernels with one extra leading grid axis (every (frame, slab) pair is an
  independent 2D scan); the third pass scans *across* slabs with a
  ``(1, 1, bm, bn)`` carry per (row-band, column-band) tile.
- A leading batch axis makes a ``(B, n1, n2, n3)`` stack one launch with
  per-frame carry reset — the same property that lets the 2D kernel lower
  under the frame-sharded planner's ``shard_map`` trace; a rank-3 input is
  the ``B=1`` case.
- Like the 2D kernel this is memory-bound by construction (three passes of
  2 x B x n1 x n2 x n3 x 4 bytes); the on-tile scan is the 2D kernel's
  exact log-step roll-and-add (``sat.tile_cumsum``, VPU), no MXU use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sat import tile_cumsum


def _scan3_kernel(x_ref, o_ref, carry_ref):
    """Prefix along axis 3 of each (1, 1, bm, bn) tile; carry (bm, 1).

    Grid: (B, slabs, row-bands, col-bands) — innermost walks the scan
    direction, so the carry holds the running right-edge column.
    """
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():  # new (frame, slab, row-band): reset the edge sums
        carry_ref[...] = jnp.zeros_like(carry_ref)

    c = tile_cumsum(x_ref[0, 0], 1) + carry_ref[...]
    o_ref[0, 0] = c
    carry_ref[...] = c[:, -1:]


def _scan2_kernel(x_ref, o_ref, carry_ref):
    """Prefix along axis 2 of each (1, 1, bm, bn) tile; carry (1, bn)."""
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    c = tile_cumsum(x_ref[0, 0], 0) + carry_ref[...]
    o_ref[0, 0] = c
    carry_ref[...] = c[-1:, :]


def _scan1_kernel(x_ref, o_ref, carry_ref):
    """running sum across slabs: carry (1, 1, bm, bn) adds the slabs so far.

    Grid: (B, row-bands, col-bands, slabs) — each tile is one whole slab's
    (bm, bn) window, and the innermost axis walks down the slab stack.
    """
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    c = x_ref[...] + carry_ref[...]
    o_ref[...] = c
    carry_ref[...] = c


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sat3_pallas(a: jnp.ndarray, *, bm: int = 128, bn: int = 256,
                interpret: bool = False) -> jnp.ndarray:
    """Inclusive 3D prefix sum via three blocked Pallas passes.

    ``a`` is ``(n1, n2, n3)`` or a batched ``(B, n1, n2, n3)`` frame
    stack; the batch dimension becomes the outermost grid axis (one
    launch, carries reset per frame), never a Python loop.
    """
    squeeze = a.ndim == 3
    x = a[None] if squeeze else a
    B, n1, n2, n3 = x.shape
    pad2 = (-n2) % bm
    pad3 = (-n3) % bn
    x = jnp.pad(x, ((0, 0), (0, 0), (0, pad2), (0, pad3)))  # zero: safe
    m2, m3 = x.shape[2], x.shape[3]

    # pass 1: prefix along axis 3 within each (frame, slab)
    pass1 = pl.pallas_call(
        _scan3_kernel,
        grid=(B, n1, m2 // bm, m3 // bn),  # innermost walks along axis 3
        in_specs=[pl.BlockSpec((1, 1, bm, bn),
                               lambda b, s, i, j: (b, s, i, j))],
        out_specs=pl.BlockSpec((1, 1, bm, bn),
                               lambda b, s, i, j: (b, s, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, n1, m2, m3), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 1), x.dtype)],
        interpret=interpret,
    )(x)

    # pass 2: prefix along axis 2 within each (frame, slab)
    pass2 = pl.pallas_call(
        _scan2_kernel,
        grid=(B, n1, m3 // bn, m2 // bm),  # innermost walks down axis 2
        in_specs=[pl.BlockSpec((1, 1, bm, bn),
                               lambda b, s, j, i: (b, s, i, j))],
        out_specs=pl.BlockSpec((1, 1, bm, bn),
                               lambda b, s, j, i: (b, s, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, n1, m2, m3), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, bn), x.dtype)],
        interpret=interpret,
    )(pass1)

    # pass 3: running sum across slabs per (row-band, col-band) window
    pass3 = pl.pallas_call(
        _scan1_kernel,
        grid=(B, m2 // bm, m3 // bn, n1),  # innermost walks the slab axis
        in_specs=[pl.BlockSpec((1, 1, bm, bn),
                               lambda b, i, j, s: (b, s, i, j))],
        out_specs=pl.BlockSpec((1, 1, bm, bn),
                               lambda b, i, j, s: (b, s, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, n1, m2, m3), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, 1, bm, bn), x.dtype)],
        interpret=interpret,
    )(pass2)

    out = pass3[:, :, :n2, :n3]
    return out[0] if squeeze else out
