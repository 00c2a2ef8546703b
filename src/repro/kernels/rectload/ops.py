"""Public jit'd wrapper for jagged-partition load evaluation."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import pallas_interpret_default

from .rectload import jagged_loads_pallas, load_dtype
from .ref import jagged_loads_ref


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def jagged_loads(gamma: jnp.ndarray, row_cuts: jnp.ndarray,
                 col_cuts: jnp.ndarray, *,
                 use_pallas: bool = True) -> jnp.ndarray:
    """Rectangle loads; accepts 2D Gamma or a leading-frame-axis batch.

    Loads are int32 for an integer Gamma and f32 otherwise.
    ``use_pallas`` selects the kernel or its jnp oracle; the kernel's
    interpret mode resolves via
    :func:`repro.backend.pallas_interpret_default`.
    """
    if not use_pallas:
        return jagged_loads_ref(gamma.astype(load_dtype(gamma)), row_cuts,
                                col_cuts)
    return jagged_loads_pallas(gamma, row_cuts, col_cuts,
                               interpret=pallas_interpret_default())
