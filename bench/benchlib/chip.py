"""The chip: refusing to run without one, its peaks, its memory, and
JAX's compile events."""
from __future__ import annotations

import json
from pathlib import Path

import jax

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def devices(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform}); "
                     f"the benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks(kind: str) -> dict:
    """Published peaks of ``kind`` (a ``device_kind``); unknown is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak(devs)}


class CompileClock:
    """JAX's own compile events since it was made: seconds spent tracing,
    lowering and compiling, compilations, and persistent-cache hits and
    misses."""

    _STAGES = frozenset({
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    })

    def __init__(self):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event in self._STAGES:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
