"""Pallas TPU kernel: batched greedy feasibility probe.

One launch evaluates a whole (stripe, candidate) grid: for stripe s and
candidate bottleneck Ls[s, k], how many greedy maximal intervals of load
<= L cover the stripe's prefix row?  This is the inner loop of every
exact bisection; fusing it keeps the SAT -> probe -> cut chain of
``jag_pq_opt_device`` device-resident end to end.

TPU-native design:

- grid ``(S,)`` — one program per stripe.  The prefix row is laid out as
  ``(S, 1, Npad)`` and the candidates as ``(S, Kp, 1)``, so each program
  holds a ``(1, Npad)`` lane row and a ``(Kp, 1)`` sublane column in VMEM
  (blocks whose trailing dims are whole array dims, as the TPU's (8, 128)
  tiling demands) and sweeps all candidates in lockstep on the VPU over
  the ``(Kp, Npad)`` broadcast — no transpose anywhere.
- ``searchsorted`` has no vector primitive, so it is recomputed as a
  masked comparison count: the furthest index with ``p <= p[pos] + L`` is
  ``sum((p <= target) & (iota <= n)) - 1`` along the lanes.  The position
  gather is the matching one-hot sum (exact: one nonzero term).  Both
  are O(N) per step instead of O(log N), but the K candidates amortize
  one row load across the whole sweep and the loop is compute-dense,
  branch-free vector code.
- the step loop is a ``fori_loop`` of exactly ``cap`` rounds: a row that
  never reaches the end (stuck on one oversize element, or needing more
  than ``cap`` intervals) naturally reports the ``cap + 1`` sentinel —
  bit-identical to ``kernels.probe.ref.probe_counts_ref`` /
  ``oned.probe_count``.

The row is padded to a multiple of 128 lanes and the candidates to a
multiple of 8 sublanes; padding columns are excluded by the ``iota <= n``
mask, padding candidates are harmless extra rows whose counts are sliced
away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _probe_kernel(p_ref, l_ref, o_ref, *, n: int, cap: int):
    p_row = p_ref[0]                         # (1, Npad)
    Ls = l_ref[0]                            # (Kp, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (Ls.shape[0], p_row.shape[1]),
                                    1)
    valid = iota <= n

    def step(_, carry):
        pos, cnt = carry                     # (Kp, 1) each
        pv = jnp.sum(jnp.where(iota == pos, p_row, 0), axis=1,
                     keepdims=True)
        target = pv + Ls
        ss = jnp.sum(((p_row <= target) & valid).astype(jnp.int32), axis=1,
                     keepdims=True) - 1
        nxt = jnp.clip(ss, pos, n)
        adv = (pos < n) & (nxt > pos)
        return jnp.where(adv, nxt, pos), cnt + adv.astype(jnp.int32)

    pos0 = jnp.zeros(Ls.shape, jnp.int32)
    pos, cnt = jax.lax.fori_loop(0, cap, step, (pos0, pos0))
    o_ref[0] = jnp.where(pos < n, cap + 1, jnp.maximum(cnt, 1))


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def probe_counts_pallas(p: jnp.ndarray, Ls: jnp.ndarray, cap: int, *,
                        interpret: bool = False) -> jnp.ndarray:
    """Greedy interval counts on device. p: (S, N+1), Ls: (S, K) -> (S, K)."""
    S, n_plus_1 = p.shape
    n = n_plus_1 - 1
    K = Ls.shape[1]
    npad = n_plus_1 + (-n_plus_1) % 128
    kp = K + (-K) % 8
    # column padding sits behind the iota mask; candidate padding is junk
    # rows sliced off below (0 is a valid L: it just reports the sentinel)
    pp = jnp.pad(p, ((0, 0), (0, npad - n_plus_1)))[:, None, :]
    lp = jnp.pad(Ls, ((0, 0), (0, kp - K)))[:, :, None]

    out = pl.pallas_call(
        functools.partial(_probe_kernel, n=n, cap=cap),
        grid=(S,),
        in_specs=[pl.BlockSpec((1, 1, npad), lambda s: (s, 0, 0)),
                  pl.BlockSpec((1, kp, 1), lambda s: (s, 0, 0))],
        out_specs=pl.BlockSpec((1, kp, 1), lambda s: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, kp, 1), jnp.int32),
        interpret=interpret,
    )(pp, lp)
    return out[:, :K, 0]
