#!/usr/bin/env python3
"""Chip smoke test: the rebalancing planner's main path, compiled, on a TPU.

One process drives the planner through the entry points a runtime calls,
at the size of a simulation's load stream, with frames made from
``--seed``, and checks every result against the repo's own references:

- A. 2D heuristic stream: ``planner.plan_iter`` with default arguments on
  16 drifting-hotspot frames of 4096 x 4096, P=32, m=1024.  Every Plan
  validates; Lmax (recomputed on the host in int64 from the device cuts)
  is within 0.1% of the same chain run on the CPU backend on two frames;
  the rectload kernel's prices (``execute.plan_rect_loads``) equal the
  host ``Plan.loads``.
- B. 2D exact stream: ``planner.plan_stream(exact=True)`` on the same
  frames in int32 (Pallas SAT and probe).  Cuts are bit-identical to the
  host ``jagged.jag_pq_opt`` on two frames, Gamma bit-identical to
  ``gamma_ref`` on one.
- C. 3D SGORP: ``planner.plan_stream_3d`` on 8 PIC volumes of 256^3,
  m=512.  Refined Lmax never exceeds the per-axis warm start; the int32
  Gamma3 kernel is bit-identical to ``gamma3_ref`` on one volume.

``--chips 4`` runs only the frame-sharded path: phases A-C through
``planner_mesh(4)``, each compared bit for bit with the one-chip plans of
the same frames, computed in the same process.

Each phase prints one line with its compile seconds (JAX's own compile
events during the first call, with the persistent cache's hits and
misses), the seconds of a second, warm call ending in
``block_until_ready``, the device's ``peak_bytes_in_use`` (the process's
peak so far) and the check's outcome.  These are smoke timings, not benchmark numbers.  The
last line of stdout is the JSON result; it is printed only when every
check passed on a TPU.  Off a TPU, or when a check fails, the script
exits non-zero and prints no result.

    python chip_smoke.py [--seed 0] [--chips 1|4]
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import backend                                  # noqa: E402
from repro.core import jagged, prefix, sgorp               # noqa: E402
from repro.dist import ctx                                 # noqa: E402
from repro.kernels.sat import ops as sat_ops               # noqa: E402
from repro.kernels.sat.ref import gamma3_ref, gamma_ref    # noqa: E402
from repro.rebalance import (batch_device, execute, planner,  # noqa: E402
                             stream)

# the deployment-sized shapes of each phase
T2, N2, P2, M2 = 16, 4096, 32, 1024
T3, N3, M3 = 8, 256, 512
CPU_FRAMES = 2      # frames re-planned on the CPU backend / host solver
# the Pallas kernels each phase's program must hold, compiled
SAT2 = {"_row_scan_kernel", "_col_scan_kernel"}
PROBE = {"_probe_kernel"}
SAT3 = {"_scan3_kernel", "_scan2_kernel", "_scan1_kernel"}


class CompileClock:
    """JAX's own compile events, counted from when it is made: seconds
    spent tracing, lowering and compiling, and hits and misses of the
    persistent compilation cache (a warm cache hides compile time)."""

    _STAGES = frozenset({
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    })

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event in self._STAGES:
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def timed(self, fn):
        """Run ``fn`` cold, then warm: (warm result, compile fields, warm s).

        ``fn`` must return only once its device work is done (host
        arrays, or device arrays passed through ``block_until_ready``).
        """
        before = (self.seconds, self.hits, self.misses)
        fn()
        compile_fields = {"compile_s": self.seconds - before[0],
                          "cache_hits": self.hits - before[1],
                          "cache_misses": self.misses - before[2]}
        t0 = time.perf_counter()
        out = fn()
        return out, compile_fields, time.perf_counter() - t0


def peak_bytes(devices) -> list[int]:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
            for d in devices]


class Phase:
    """Collects one phase's checks and prints its line."""

    def __init__(self, name: str):
        self.name, self.checks, self.failed = name, [], []

    def check(self, what: str, ok: bool, detail: str = "") -> None:
        self.checks.append(what)
        if not ok:
            self.failed.append(f"{what} {detail}".strip())

    def report(self, compiled, run_s, devices, **extra) -> None:
        outcome = ("PASS" if not self.failed
                   else "FAIL: " + "; ".join(self.failed))
        fields = {**compiled, "run_s": run_s,
                  "peak_bytes_in_use": peak_bytes(devices), **extra,
                  "checks": len(self.checks), "outcome": outcome}
        print(f"[{self.name}] (smoke timings) "
              + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)
        if self.failed:
            raise SystemExit(f"phase {self.name} failed: {outcome}")


def kernels_in(jitted, *args, **kw) -> set[str]:
    """Names of the compiled (not interpreted) Pallas kernels in the
    program ``jitted`` lowers to for these arguments."""
    txt = jitted.lower(*args, **kw).as_text()
    if "tpu_custom_call" not in txt:
        return set()
    return set(re.findall(r"kernel_name\W+(\w+)", txt))


def host_frames_2d(seed: int, T: int, n: int) -> np.ndarray:
    f = stream.drifting_hotspot(T, n, n, seed=seed)
    if f.sum(axis=(1, 2)).max() >= 2 ** 31:
        raise ValueError("a frame's total load does not fit int32")
    return f.astype(np.int32)


def host_frames_3d(seed: int, T: int, n: int) -> np.ndarray:
    return stream.pic_series_3d(T, n, n, n, seed=seed).astype(np.int32)


# ---------------------------------------------------------------------------
# one chip: the main path against its references


def phase_a(frames, *, P: int, m: int, devices, clock) -> None:
    ph = Phase("A heuristic plan_iter")
    T = frames.shape[0]
    plans, compiled, run_s = clock.timed(
        lambda: list(planner.plan_iter(frames, P=P, m=m)))
    kernels = kernels_in(batch_device.plan_stream,
                         jax.ShapeDtypeStruct(frames.shape, jnp.int32),
                         P=P, m=m)
    ph.check("compiled SAT kernels", kernels >= SAT2, f"{kernels}")
    lmax, priced = [], 0
    for t, plan in enumerate(plans):
        g = prefix.prefix_sum_2d(frames[t])
        plan.validate(g, m=m)  # raises on a malformed plan
        loads = plan.loads(g)
        lmax.append(int(loads.max()))
        rect = execute.plan_rect_loads(plan, weights=frames[t])
        priced += int(np.array_equal(rect, loads))
    ph.check("rectload == Plan.loads", priced == T, f"{priced}/{T}")
    with jax.default_device(jax.devices("cpu")[0]):
        cpu_plans = list(planner.plan_iter(frames[:CPU_FRAMES], P=P, m=m))
    rel = []
    for t, plan in enumerate(cpu_plans):
        ref = int(plan.loads(prefix.prefix_sum_2d(frames[t])).max())
        rel.append(abs(lmax[t] - ref) / ref)
    ph.check("Lmax within 0.1% of CPU", max(rel) <= 1e-3, f"rel={rel}")
    ph.report(compiled, run_s, devices, kernels=sorted(kernels), frames=T,
              lmax_min=min(lmax), lmax_max=max(lmax),
              lmax_rel_vs_cpu=max(rel))


def phase_b(frames, *, P: int, m: int, devices, clock) -> None:
    ph = Phase("B exact plan_stream")
    T, n1, n2 = frames.shape
    totals = frames.sum(axis=(1, 2), dtype=np.int64)
    print(f"[B] frame total loads: {totals.tolist()}", flush=True)
    ph.check("totals < 2**31", int(totals.max()) < 2 ** 31)

    def run():
        return jax.block_until_ready(
            planner.plan_stream(frames, P=P, m=m, exact=True))

    out, compiled, run_s = clock.timed(run)
    kernels = kernels_in(batch_device.plan_stream,
                         jax.ShapeDtypeStruct(frames.shape, jnp.int32),
                         P=P, m=m, exact=True)
    ph.check("compiled SAT + probe kernels", kernels >= SAT2 | PROBE,
             f"{kernels}")
    plans = batch_device.unstack_plans(out, (n1, n2))
    lmax_dev = np.asarray(out[3])
    for t in range(CPU_FRAMES):
        g = prefix.prefix_sum_2d(frames[t])
        host = jagged.jag_pq_opt(g, m, P=P, Q=m // P, orient="hor")
        dev = plans[t].to_partition()
        ph.check(f"cuts == host jag_pq_opt frame {t}",
                 dev.rects == host.rects)
        ph.check(f"Lmax == host frame {t}",
                 int(lmax_dev[t]) == int(host.max_load(g)))
    x = jnp.asarray(frames[:1])
    got = np.asarray(planner.sat_stage(x))
    ph.check("Gamma == gamma_ref", np.array_equal(
        got, np.asarray(gamma_ref(x))))
    ph.check("Gamma == host prefix", np.array_equal(
        got[0], prefix.prefix_sum_2d(frames[0])))
    ph.report(compiled, run_s, devices, kernels=sorted(kernels), frames=T,
              lmax_max=int(lmax_dev.max()))


def grid_lmax(g3, cuts) -> int:
    """Exact Lmax of a rectilinear grid on an int64 host Gamma3."""
    sub = g3[np.ix_(*[np.asarray(c) for c in cuts])]
    for ax in range(3):
        sub = np.diff(sub, axis=ax)
    return int(sub.max())


def phase_c(frames, *, m: int, devices, clock) -> None:
    ph = Phase("C sgorp plan_stream_3d")
    T = frames.shape[0]
    grid = sgorp.default_grid(m, frames.shape[1:])

    def run():
        return jax.block_until_ready(planner.plan_stream_3d(frames, m=m))

    out, compiled, run_s = clock.timed(run)
    kernels = kernels_in(
        jax.jit(functools.partial(planner.plan_frames_3d, grid=grid)),
        jax.ShapeDtypeStruct(frames.shape, jnp.int32))
    ph.check("compiled SAT3 kernels", kernels >= SAT3, f"{kernels}")

    def warm_start(f):  # what the planner descends from, on its own Gamma
        g = sat_ops.gamma3_impl(f.astype(jnp.float32),
                                use_pallas=backend.use_pallas_default())
        return jax.vmap(functools.partial(sgorp.warm_start_impl,
                                          grid=grid))(g)

    warm = jax.jit(warm_start)(jnp.asarray(frames))
    refined, start = [], []
    for t in range(T):
        g3 = prefix.prefix_sum_3d(frames[t])
        refined.append(grid_lmax(g3, [c[t] for c in out[:3]]))
        start.append(grid_lmax(g3, [c[t] for c in warm]))
    ok = sum(r <= w for r, w in zip(refined, start))
    ph.check("refined Lmax <= warm start", ok == T, f"{ok}/{T}")
    # one volume, halved so its exact total fits int32
    v = frames[0] // 2
    x = jnp.asarray(v)
    got = np.asarray(sat_ops.gamma3(x))
    ph.check("int32 Gamma3 == gamma3_ref", np.array_equal(
        got, np.asarray(gamma3_ref(x))))
    ph.check("int32 Gamma3 == host prefix", np.array_equal(
        got, prefix.prefix_sum_3d(v)))
    ph.report(compiled, run_s, devices, kernels=sorted(kernels), frames=T,
              grid=list(grid),
              iters=np.asarray(out[4]).tolist(), refined_lmax=refined,
              warm_lmax=start)


# ---------------------------------------------------------------------------
# four chips: the frame-sharded path against one-chip plans


def _identical(sharded, single) -> int:
    """Frames whose every output (cuts, counts, Lmax, ...) is bit-identical
    between two per-frame sequences of array tuples."""
    return sum(all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b)) for a, b in zip(sharded, single))


def _per_frame(out):
    """A stacked (T, ...) output pytree as T per-frame tuples."""
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]
    return [tuple(x[t] for x in leaves) for t in range(leaves[0].shape[0])]


def _shard_devices(out) -> list[int]:
    """Ids of the devices holding shards of a sharded result."""
    return sorted({s.device.id for s in out.addressable_shards})


def _compare(ph, sharded, single, held, D) -> dict:
    n = _identical(sharded, single)
    ph.check(f"{D}-chip cuts == 1-chip", n == len(single),
             f"{n}/{len(single)}")
    ph.check(f"shards on {D} devices", len(held) == D, f"{held}")
    return {"identical_frames": f"{n}/{len(single)}", "shard_devices": held}


def sharded_phases(frames2, frames3, *, mesh, devices, clock) -> None:
    D = mesh.devices.size

    def cuts(plans):
        return [(p.row_cuts, p.counts, p.col_cuts) for p in plans]

    ph = Phase(f"A heuristic plan_iter D{D}")
    one = cuts(planner.plan_iter(frames2, P=P2, m=M2))
    sh, compiled, run_s = clock.timed(
        lambda: cuts(planner.plan_iter(frames2, P=P2, m=M2, mesh=mesh)))
    held = _shard_devices(planner.plan_stream(frames2[:D], P=P2, m=M2,
                                              mesh=mesh)[0])
    ph.report(compiled, run_s, devices, **_compare(ph, sh, one, held, D))

    ph = Phase(f"B exact plan_stream D{D}")
    one = jax.block_until_ready(
        planner.plan_stream(frames2, P=P2, m=M2, exact=True))
    sh, compiled, run_s = clock.timed(lambda: jax.block_until_ready(
        planner.plan_stream(frames2, P=P2, m=M2, exact=True, mesh=mesh)))
    ph.report(compiled, run_s, devices, **_compare(
        ph, _per_frame(sh), _per_frame(one), _shard_devices(sh[0]), D))

    ph = Phase(f"C sgorp plan_stream_3d D{D}")
    one = jax.block_until_ready(planner.plan_stream_3d(frames3, m=M3))
    sh, compiled, run_s = clock.timed(lambda: jax.block_until_ready(
        planner.plan_stream_3d(frames3, m=M3, mesh=mesh)))
    ph.report(compiled, run_s, devices, **_compare(
        ph, _per_frame(sh), _per_frame(one), _shard_devices(sh[0]), D))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache = backend.enable_compile_cache()
    clock = CompileClock()
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"jax {jax.__version__}, compile cache {cache}, "
          f"pallas compiled={not backend.pallas_interpret_default()}",
          flush=True)

    t0 = time.perf_counter()
    frames2 = host_frames_2d(args.seed, T2, N2)
    frames3 = host_frames_3d(args.seed, T3, N3)
    print(f"chip_smoke: frames made in {time.perf_counter() - t0:.1f} s "
          f"(set-up)", flush=True)

    if args.chips == 1:
        used = devices[:1]
        phase_a(frames2, P=P2, m=M2, devices=used, clock=clock)
        phase_b(frames2, P=P2, m=M2, devices=used, clock=clock)
        phase_c(frames3, m=M3, devices=used, clock=clock)
    else:
        used = devices[:args.chips]
        sharded_phases(frames2, frames3, mesh=ctx.planner_mesh(args.chips),
                       devices=used, clock=clock)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
