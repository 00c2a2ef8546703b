"""Pallas TPU kernel: jagged-partition rectangle loads from Gamma.

Evaluating the loads of all m rectangles of a jagged partition is the inner
loop of every probe/refinement step. On GPU this is a scatter/gather; TPUs
dislike arbitrary gathers, so we restructure it TPU-natively:

- **Data-dependent row blocks via scalar prefetch**: the stripe boundaries
  ``row_cuts`` are a scalar-prefetch operand, and the BlockSpec index_map
  picks the (8, bn) Gamma row block that holds each row a stripe needs —
  the DMA engine streams 2 x (8, bn) per grid step out of HBM, never the
  full table, and the kernel selects the wanted sublane with a mask.  An
  8-row block keeps Gamma in its natural (n1+1, n2+1) layout: a
  ``(1, bn)`` block is refused by the TPU's (8, 128) tiling, and
  reshaping to ``(n1+1, 1, n2+1)`` would pad each row to 8 sublanes in
  HBM (8x the bytes).
- **Gather -> masked select-sum on the VPU**: the stripe's load over
  interval q is ``chunk[cc[q+1]] - chunk[cc[q]]`` with ``chunk`` the
  stripe's prefix row; each term is one ``where(j == cut, chunk, 0)``
  lane reduction per (stripe, column block), so exactly one nonzero is
  ever summed.  That keeps int32 Gammas exact (the MXU is not exact for
  int32, and runs f32 through bf16 passes at default precision) and
  makes f32 results bit-identical to the same differences on the host.
- **Leading frame axis**: a ``(B, n1+1, n2+1)`` Gamma stack with per-frame
  cut tables is one kernel launch with grid ``(B, P, n_col_blocks)`` —
  mirroring ``kernels.sat`` — so the rebalancing executor can price every
  frame's adopted plan in a single dispatch.  A 2D input is the ``B=1``
  case (squeezed on the way out).

Grid: (B, P, n_col_blocks); the column-block axis is innermost and
accumulates into the (Q, 1) output block for the (frame, stripe).
Loads are int32 for integer Gammas and f32 otherwise
(:func:`load_dtype`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def load_dtype(gamma: jnp.ndarray):
    """Accumulator of the loads: int32 for integer Gammas, else f32."""
    return jnp.int32 if jnp.issubdtype(gamma.dtype, jnp.integer) \
        else jnp.float32


def _kernel(row_cuts_ref, g_lo_ref, g_hi_ref, cc_lo_ref, cc_hi_ref, o_ref, *,
            bn: int, n_cols: int):
    b, s, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sub = jax.lax.broadcasted_iota(jnp.int32, (8, bn), 0)

    def row(g_ref, r):  # the wanted sublane of the 8-row block, (1, bn)
        blk = g_ref[0]
        return jnp.sum(jnp.where(sub == r % 8, blk, 0), axis=0,
                       keepdims=True)

    chunk = row(g_hi_ref, row_cuts_ref[b, s + 1]) \
        - row(g_lo_ref, row_cuts_ref[b, s])
    jglob = c * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    # guard the zero-pad tail: indices past n_cols never match a cut
    jglob = jnp.where(jglob < n_cols, jglob, -2)

    def pick(cut_ref):  # (Q, 1): chunk at each stripe's cut column
        return jnp.sum(jnp.where(jglob == cut_ref[0, 0], chunk, 0), axis=1,
                       keepdims=True)

    o_ref[0, 0] += pick(cc_hi_ref) - pick(cc_lo_ref)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def jagged_loads_pallas(gamma: jnp.ndarray, row_cuts: jnp.ndarray,
                        col_cuts: jnp.ndarray, *, bn: int = 512,
                        interpret: bool = False) -> jnp.ndarray:
    """Rectangle loads of a jagged partition; see module docstring.

    ``gamma`` is ``(n1+1, n2+1)`` with ``row_cuts (P+1,)`` /
    ``col_cuts (P, Q+1)`` -> ``(P, Q)``, or a batched
    ``(B, n1+1, n2+1)`` stack with ``(B, P+1)`` / ``(B, P, Q+1)`` cuts
    -> ``(B, P, Q)``; the frame axis is the outermost grid axis of a
    single launch, never a Python loop.
    """
    squeeze = gamma.ndim == 2
    g = gamma[None] if squeeze else gamma
    rc = row_cuts[None] if squeeze else row_cuts
    cc = col_cuts[None] if squeeze else col_cuts
    B, n1p, n2p = g.shape
    P = rc.shape[1] - 1
    Q = cc.shape[2] - 1
    dt = load_dtype(g)
    g = jnp.pad(g.astype(dt), ((0, 0), (0, (-n1p) % 8), (0, (-n2p) % bn)))
    ncb = g.shape[2] // bn
    # cuts ride the sublanes, (Q, 1) per (frame, stripe), so the kernel
    # compares them against a lane row of column indices without a
    # transpose
    cc = cc.astype(jnp.int32)[..., None]
    cc_lo, cc_hi = cc[:, :, :-1], cc[:, :, 1:]

    def row_block(off):
        return pl.BlockSpec((1, 8, bn),
                            lambda b, s, c, rc: (b, rc[b, s + off] // 8, c))

    cut_spec = pl.BlockSpec((1, 1, Q, 1), lambda b, s, c, rc: (b, s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P, ncb),
        # the Gamma row block below the stripe (row_cuts[b, s]) and at the
        # top of the next one (row_cuts[b, s + 1]), then the stripe's cuts
        in_specs=[row_block(0), row_block(1), cut_spec, cut_spec],
        out_specs=cut_spec,
    )
    kernel = pl.pallas_call(
        functools.partial(_kernel, bn=bn, n_cols=n2p),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, Q, 1), dt),
        interpret=interpret,
    )
    out = kernel(rc.astype(jnp.int32), g, g, cc_lo, cc_hi)[..., 0]
    return out[0] if squeeze else out
