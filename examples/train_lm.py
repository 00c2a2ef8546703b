"""End-to-end LM training: a ~100M-param qwen3-family model for a few
hundred steps on synthetic Markov data, with checkpoint/restart.

(On this CPU container we default to fewer steps / smaller width; pass
--steps 300 --d-model 768 for the full run. The loop, checkpointing and
data pipeline are identical to the production driver.)

    PYTHONPATH=src python examples/train_lm.py --steps 120
"""
import argparse

from repro import backend
from repro.launch import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    backend.enable_compile_cache()

    import repro.configs as configs
    import repro.configs.qwen3_0_6b as q
    import repro.models.api as api

    cfg = q.CONFIG.scaled(
        n_layers=args.layers, d_model=args.d_model,
        n_heads=max(args.d_model // 64, 1), n_kv_heads=max(
            args.d_model // 128, 1),
        head_dim=64, d_ff=args.d_model * 3, vocab_size=512,
        q_chunk=64, kv_chunk=64)
    print(f"model: {api.count_params(cfg) / 1e6:.1f}M params")

    # route through the production driver with an ad-hoc arch
    import repro.configs
    repro.configs.ARCHS.append("example_lm")
    import sys, types
    mod = types.ModuleType("repro.configs.example_lm")
    mod.CONFIG = cfg
    mod.SMOKE = cfg
    sys.modules["repro.configs.example_lm"] = mod

    out = train.main([
        "--arch", "example_lm", "--steps", str(args.steps),
        "--batch", "8", "--seq", "128", "--lr", "3e-3",
        "--warmup", "5", "--wd", "0.0",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
    ])
    drop = out["first_loss"] - out["last_loss"]
    print(f"loss {out['first_loss']:.3f} -> {out['last_loss']:.3f} "
          f"(drop {drop:.3f})")
    if args.steps >= 100:
        assert drop > 0.3, "training did not learn"
    else:
        assert drop > 0.1, "training did not learn"


if __name__ == "__main__":
    main()
