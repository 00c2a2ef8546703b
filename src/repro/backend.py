"""Platform-resolved execution defaults and the persistent compile cache.

The platform picks the kernel.  No function on the planning path takes
a kernel choice: each place that can run a Pallas kernel asks
:func:`use_pallas_default` where it calls it, and each kernel wrapper
asks :func:`pallas_interpret_default` for Pallas's interpret mode:

- on a TPU the kernels run compiled — the SAT stage and the exact
  solver's probe are the shipped kernels, never the jnp fallback or the
  interpreter;
- anywhere else the planner keeps its jnp path and any kernel that is
  called explicitly runs in interpret mode.

``JAX_PALLAS_INTERPRET=1`` (``=0``) forces interpret mode on (off) for
every kernel.  The platform is that of ``jax.default_device`` when one
is set (so ``with jax.default_device(jax.devices("cpu")[0])`` runs the
CPU chain in a TPU process), else the default backend's; both are part
of jit's cache key, so resolving at trace time is safe.  Tests that force
the kernels off the chip patch :func:`use_pallas_default` where it is
called and clear jit's caches around the patch.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["platform", "pallas_interpret_default", "use_pallas_default",
           "enable_compile_cache"]

# the checkout this package runs from: src/repro/backend.py -> <repo>
_REPO = Path(__file__).resolve().parents[2]


def platform() -> str:
    """Platform of the device the next computation lands on."""
    d = jax.config.jax_default_device
    if d is None:
        return jax.default_backend()
    return d if isinstance(d, str) else d.platform


def pallas_interpret_default() -> bool:
    """Resolve interpret mode: env override, else interpret off-TPU."""
    v = os.environ.get("JAX_PALLAS_INTERPRET")
    if v is not None:
        return v != "0"
    return platform() != "tpu"


def use_pallas_default() -> bool:
    """Whether the planner takes its Pallas kernels: on a TPU, yes."""
    return platform() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it at start-up and nothing here overrides it).  Otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache`` (git-ignored): the path
    is part of what makes an entry findable again, so it is never built
    from a temp name, a pid or the time.  Entry points (``chip_smoke.py``,
    the examples) call this; importing the package never does.
    """
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(_REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d
