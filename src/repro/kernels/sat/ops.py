"""Public jit'd wrappers around the SAT kernel.

``sat_impl`` / ``gamma_impl`` are the unjitted bodies: stages that compose
several kernels under one jit (``repro.rebalance.planner``) call these so
the whole pipeline stays a single jit boundary; ``sat`` / ``gamma`` are
the standalone jitted entry points.  Both accept a ``(n1, n2)`` frame or
a ``(B, n1, n2)`` stack — the batch dimension rides the kernel's leading
grid axis (or the oracle's trailing-axes cumsum), so batched/sharded
traces never fall back to a per-frame Python loop.

``use_pallas`` selects the kernel or its jnp oracle.  The kernel's
interpret mode is not a parameter: it resolves through
:func:`repro.backend.pallas_interpret_default` (compiled on a TPU,
interpreted elsewhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import pallas_interpret_default

from .ref import gamma3_from_sat, gamma_from_sat, sat3_ref, sat_ref
from .sat import sat_pallas
from .sat3d import sat3_pallas


def sat_impl(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return sat_ref(a)
    return sat_pallas(a, interpret=pallas_interpret_default())


def gamma_impl(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    return gamma_from_sat(sat_impl(a, use_pallas=use_pallas))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def sat(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Inclusive 2D prefix sum of a ``(n1, n2)`` frame or a
    ``(B, n1, n2)`` stack."""
    return sat_impl(a, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def gamma(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """The paper's Gamma array: exclusive prefix, shape (..., n1+1, n2+1)."""
    return gamma_impl(a, use_pallas=use_pallas)


# --- rank-3 twins.  Separate names (not an overload of ``sat``) because a
# rank-3 array is ambiguous: (B, n1, n2) 2D stack vs (n1, n2, n3) volume.

def sat3_impl(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return sat3_ref(a)
    return sat3_pallas(a, interpret=pallas_interpret_default())


def gamma3_impl(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    return gamma3_from_sat(sat3_impl(a, use_pallas=use_pallas))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def sat3(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Inclusive 3D prefix sum of a ``(n1, n2, n3)`` volume or a
    ``(B, n1, n2, n3)`` frame stack."""
    return sat3_impl(a, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def gamma3(a: jnp.ndarray, *, use_pallas: bool = True) -> jnp.ndarray:
    """Exclusive 3D prefix, shape (..., n1+1, n2+1, n3+1)."""
    return gamma3_impl(a, use_pallas=use_pallas)
