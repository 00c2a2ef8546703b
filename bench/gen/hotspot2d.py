"""Drifting Gaussian hotspots over a 2D grid, made on the device.

A port of the planner's host generator (``stream.drifting_hotspot``) to
``jax.random``: hotspots start at points in [0.15, 0.85]^2 and travel in
straight lines, reflecting off the walls, crossing ``speed`` of the grid
over the pool's ``T`` frames; the density ``base * (1 + amplitude *
sum_h gauss_h)`` is Poisson-sampled and floored at 1.

The hotspot paths come from the configuration's ``geometry_seed``, so
every run sees the same drift; ``--seed`` draws the Poisson noise of each
frame.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT32_TOTAL_LIMIT = 2 ** 31


def frame(cfg: dict, key, t, T: int):
    """Frame ``t`` (traced int) of a ``T``-frame stream: (n1, n2) int32."""
    n1, n2 = cfg["frame"]["n1"], cfg["frame"]["n2"]
    H = cfg["hotspots"]
    g1, g2 = jax.random.split(jax.random.key(cfg["geometry_seed"]))
    pos = jax.random.uniform(g1, (H, 2), minval=0.15, maxval=0.85)
    ang = jax.random.uniform(g2, (H,), minval=0.0, maxval=2 * jnp.pi)
    vel = jnp.stack([jnp.cos(ang), jnp.sin(ang)], axis=1) \
        * cfg["speed"] / max(T - 1, 1)
    q = (pos + vel * t) % 2.0
    q = jnp.where(q > 1.0, 2.0 - q, q)
    w2 = 2.0 * cfg["width"] ** 2
    ii = jnp.arange(n1, dtype=jnp.float32) / n1
    jj = jnp.arange(n2, dtype=jnp.float32) / n2
    gi = jnp.exp(-(ii[None, :] - q[:, :1]) ** 2 / w2)       # (H, n1)
    gj = jnp.exp(-(jj[None, :] - q[:, 1:]) ** 2 / w2)       # (H, n2)
    dens = jnp.einsum("hi,hj->ij", gi, gj,
                      precision=jax.lax.Precision.HIGHEST)
    field = cfg["base"] * (1.0 + cfg["amplitude"] * dens)
    sample = jax.random.poisson(jax.random.fold_in(key, t), field,
                                dtype=jnp.int32)
    return jnp.maximum(sample, 1)


def total_ok(frames) -> jnp.ndarray:
    """Whether every frame's total load fits the int32 accumulators of
    the exact solver (summed in float32, far from the limit here)."""
    tot = frames.astype(jnp.float32).sum(axis=tuple(range(1, frames.ndim)))
    return jnp.all(tot < 0.99 * INT32_TOTAL_LIMIT)
