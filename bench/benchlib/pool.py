"""The frame pool: every frame the window replays, made on the device
from ``--seed``, laid out as one array per call of the entry point."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (JAX keys take 32)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def make(gen, cfg: dict, traffic: dict, seed: int) -> list:
    """``pool_calls`` device arrays of ``frames_per_call`` frames each,
    made by one jitted program called once per array; frame ``t`` of the
    stream is frame ``t % frames_per_call`` of array ``t //
    frames_per_call``.  Raises when a frame breaks the generator's limits
    (``gen.total_ok``)."""
    n_calls, per_call = traffic["pool_calls"], traffic["frames_per_call"]
    T = n_calls * per_call

    @jax.jit
    def body(words, t0):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        ts = t0 + jnp.arange(per_call, dtype=jnp.int32)
        out = jax.lax.map(lambda t: gen.frame(cfg, key, t, T), ts)
        return out, gen.total_ok(out)

    words = jnp.asarray(seed_words(seed))
    frames, oks = [], []
    for c in range(n_calls):
        out, ok = body(words, jnp.int32(c * per_call))
        frames.append(out)
        oks.append(ok)
    if not all(bool(ok) for ok in oks):
        raise ValueError("a generated frame breaks the configuration's "
                         "accumulator limit")
    return frames


class HostFrames:
    """``frame(t)``: frame ``t`` of the pool on the host, each pool array
    copied once, on first use."""

    def __init__(self, pool: list, per_call: int):
        self.pool, self.per_call, self.cache = pool, per_call, {}

    def __call__(self, t: int) -> np.ndarray:
        c, j = divmod(t, self.per_call)
        if c not in self.cache:
            self.cache[c] = np.asarray(self.pool[c])
        return self.cache[c][j]
