"""Quickstart: partition a spatial workload and compare algorithm classes.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import backend
from repro.core import prefix, registry


def main():
    backend.enable_compile_cache()
    # a PIC-MAG-like particle density on a 256x256 grid
    A = prefix.pic_like_instance(256, 256, iteration=20_000)
    gamma = prefix.prefix_sum_2d(A)
    m = 1024  # processors

    print(f"load matrix {A.shape}, total={A.sum():,}, "
          f"Delta={A.max() / A.min():.2f}, m={m}\n")
    print(f"{'algorithm':20s} {'LI %':>8s} {'rects':>6s}")
    for name in ["rect-uniform", "rect-nicol", "jag-pq-heur", "jag-pq-opt",
                 "jag-pq-opt-device", "jag-m-heur", "jag-m-heur-probe",
                 "hier-rb", "hier-relaxed", "hybrid"]:
        part = registry.partition(name, gamma, m)
        assert part.is_valid()
        print(f"{name:20s} {part.load_imbalance(gamma) * 100:8.2f} "
              f"{len(part.rects):6d}")

    # on-device (jittable) variant — the TPU-native path
    import jax.numpy as jnp
    from repro.core import device
    rc, counts, cc, Lmax = device.jag_m_heur_device(
        jnp.asarray(gamma, jnp.float32), P=32, m=m)
    li = float(Lmax) / (A.sum() / m) - 1
    print(f"{'jag-m-heur (device)':20s} {li * 100:8.2f} {m:6d}")


if __name__ == "__main__":
    main()
