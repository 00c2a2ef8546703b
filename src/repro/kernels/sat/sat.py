"""Pallas TPU kernel: blocked summed-area table (2D inclusive prefix sum).

The SAT is the paper's fundamental data structure (Section 2.1): once built,
any rectangle load is four lookups. The paper builds it on the host (~40 ms
for 512x512); for on-device rebalancing of large grids we build it on-TPU.

TPU-native design (HBM -> VMEM -> VREG):
- Two separable passes: row-scan (prefix along the last axis) then
  column-scan (prefix along the row axis). Each pass is a single
  ``pl.pallas_call`` whose grid walks tiles; the *innermost* grid axis
  advances along the scan direction, and a VMEM scratch carries the running
  tile-edge sums between consecutive grid steps (TPU grids execute
  sequentially, so the carry is well-defined).
- The grid carries a *leading batch axis*: a ``(B, n1, n2)`` frame stack is
  one kernel launch with grid ``(B, rows, cols)``, each frame's carry
  re-initialized when its innermost scan index restarts. This is what lets
  the frame-sharded rebalancing planner keep the Pallas path under a
  batched (vmap/shard_map) trace instead of falling back to the jnp oracle
  — a 2D input is just the ``B=1`` case.
- Tile shapes are multiples of the (8, 128) f32 VREG tiling; the default
  (256, 512) f32 tile is 512 KiB, comfortably inside the ~16 MiB VMEM even
  with input+output+carry resident.
- The on-tile scan is a log-step (Hillis-Steele) shift-and-add:
  ``log2(extent)`` rounds of ``pltpu.roll`` plus an ``iota >= shift`` mask
  on the VPU.  Mosaic has no ``cumsum`` lowering, and a triangular-ones
  matmul would go through the MXU, which is not exact for int32 and runs
  f32 at default precision through bf16 passes; adds of exact integers
  stay exact, so int32 results are bit-identical to the jnp oracle.  The
  kernel is memory-bound by construction, moving 2 x B x n1 x n2 x 4
  bytes per pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tile_cumsum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive prefix sum of a 2D tile along ``axis`` (0 or 1).

    Hillis-Steele: round ``s`` adds the tile shifted by ``s`` along the
    axis, with the wrapped-around head masked to zero — ``ceil(log2(n))``
    rolls, selects and adds, all on the VPU.
    """
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < n:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _row_scan_kernel(x_ref, o_ref, carry_ref):
    """Prefix along axis 2 of each (1, bm, bn) tile; carry: (bm, 1)."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():  # new (frame, row-band): reset the running edge sums
        carry_ref[...] = jnp.zeros_like(carry_ref)

    c = tile_cumsum(x_ref[0], 1) + carry_ref[...]
    o_ref[0] = c
    carry_ref[...] = c[:, -1:]


def _col_scan_kernel(x_ref, o_ref, carry_ref):
    """Prefix along axis 1 of each (1, bm, bn) tile; carry: (1, bn)."""
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    c = tile_cumsum(x_ref[0], 0) + carry_ref[...]
    o_ref[0] = c
    carry_ref[...] = c[-1:, :]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def sat_pallas(a: jnp.ndarray, *, bm: int = 256, bn: int = 512,
               interpret: bool = False) -> jnp.ndarray:
    """Inclusive 2D prefix sum via two blocked Pallas passes.

    ``a`` is ``(n1, n2)`` or a batched ``(B, n1, n2)`` frame stack; the
    batch dimension becomes the outermost grid axis (one launch, carries
    reset per frame), never a Python loop.
    """
    squeeze = a.ndim == 2
    x = a[None] if squeeze else a
    B, n1, n2 = x.shape
    pad1 = (-n1) % bm
    pad2 = (-n2) % bn
    x = jnp.pad(x, ((0, 0), (0, pad1), (0, pad2)))  # zero pad: prefix-safe
    m1, m2 = x.shape[1], x.shape[2]

    pass1 = pl.pallas_call(
        _row_scan_kernel,
        grid=(B, m1 // bm, m2 // bn),  # innermost walks along columns
        in_specs=[pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j))],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m1, m2), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 1), x.dtype)],
        interpret=interpret,
    )(x)

    pass2 = pl.pallas_call(
        _col_scan_kernel,
        grid=(B, m2 // bn, m1 // bm),  # innermost walks down rows
        in_specs=[pl.BlockSpec((1, bm, bn), lambda b, j, i: (b, i, j))],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, j, i: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m1, m2), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, bn), x.dtype)],
        interpret=interpret,
    )(pass1)

    out = pass2[:, :n1, :n2]
    return out[0] if squeeze else out
