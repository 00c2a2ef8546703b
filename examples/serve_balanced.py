"""Batched serving with partition-balanced scheduling.

A qwen3-family smoke model serves a heterogeneous request batch across
simulated data-parallel replicas. The batcher assigns request ranges with
the paper's 1D partitioners; we decode real tokens and compare the
simulated makespan (max replica load) of DirectCut vs optimal vs naive
round-robin, plus a straggler-rebalance event.

    PYTHONPATH=src python examples/serve_balanced.py
"""
import numpy as np
import jax
import jax.numpy as jnp

import repro.configs as configs
from repro import backend
from repro.models import api
from repro.serve import batcher


def main():
    backend.enable_compile_cache()
    rng = np.random.default_rng(0)
    cfg = configs.get_smoke("qwen3_0_6b")
    model = api.build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # 64 requests with zipf-ish prompt lengths
    lens = np.minimum((rng.pareto(1.5, 64) * 24 + 8).astype(int), 192)
    reqs = [batcher.Request(i, int(l)) for i, l in enumerate(lens)]
    R = 8

    naive = [batcher.Assignment(r, [q for j, q in enumerate(reqs)
                                    if j % R == r]) for r in range(R)]
    for name, plan in [
        ("round-robin", naive),
        ("direct-cut", batcher.plan(reqs, R, algo="direct")),
        ("optimal", batcher.plan(reqs, R, algo="optimal")),
    ]:
        loads = [a.load for a in plan]
        print(f"{name:12s} makespan={max(loads):5d} tokens "
              f"LI={batcher.imbalance(plan) * 100:6.2f}%")

    # actually decode a couple of tokens for the first replica's batch
    plan = batcher.plan(reqs, R, algo="optimal")
    group = plan[0].requests[:4]
    B = len(group)
    prompts = [rng.integers(0, cfg.vocab_size, r.prompt_tokens)
               for r in group]
    S = max(len(p) for p in prompts)
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, S - len(p):] = p  # left-pad
    cache = model.init_cache(B, S + 16)
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(toks)},
                                  cache)
    out_tokens = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for t in range(8):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, cache = model.decode(
            params, tok, jnp.full((B,), S + t, jnp.int32), cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    print(f"decoded {len(out_tokens)} tokens x {B} requests on replica 0:",
          np.stack(out_tokens, 1).tolist())

    # straggler: replica 3 reports no progress -> steal its work
    progress = [1.0, 1.0, 1.0, 0.0] + [1.0] * (R - 4)
    re = batcher.straggler_rebalance(plan, progress)
    print(f"straggler rebalance: {sum(len(a.requests) for a in re)} "
          f"requests redistributed, new LI={batcher.imbalance(re) * 100:.2f}%")


if __name__ == "__main__":
    main()
