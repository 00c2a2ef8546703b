"""Public wrappers around the probe kernel.

``probe_counts_impl`` is the unjitted body for pipelines that fuse the
probe under an enclosing jit (``core.device``'s exact solvers call it
inside their ``while_loop`` bodies); ``probe_counts`` is the standalone
jitted entry point.  ``use_pallas`` selects the kernel or its jnp
oracle; the kernel's interpret mode resolves through
:func:`repro.backend.pallas_interpret_default`: compiled on a TPU,
interpreted elsewhere, ``JAX_PALLAS_INTERPRET`` overriding both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import pallas_interpret_default

from .probe import probe_counts_pallas
from .ref import probe_counts_ref


def probe_counts_impl(p: jnp.ndarray, Ls: jnp.ndarray, cap: int, *,
                      use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return probe_counts_ref(p, Ls, cap)
    return probe_counts_pallas(p, Ls, cap,
                               interpret=pallas_interpret_default())


@functools.partial(jax.jit, static_argnames=("cap", "use_pallas"))
def probe_counts(p: jnp.ndarray, Ls: jnp.ndarray, cap: int, *,
                 use_pallas: bool = True) -> jnp.ndarray:
    """Greedy interval counts per (stripe, candidate): (S, N+1) x (S, K)
    -> (S, K) int32, ``cap + 1`` marking infeasible rows.  See
    ``ref.probe_counts_ref`` for the exact semantics contract."""
    return probe_counts_impl(p, Ls, cap, use_pallas=use_pallas)
