"""Figure 6(b,c): FedDF is undemanding on distillation-set size (1% of
data already works) and a moderate number of distillation steps approaches
optimal performance."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import default_problem, emit, fl_cfg, fusion_cfg, scale
from repro.core import mlp, run_federated
from repro.data import UnlabeledDataset


def run(seed: int = 0) -> dict:
    rounds = scale(4, 10)
    t0 = time.time()
    train, val, test, parts, _ = default_problem(seed=seed, alpha=1.0)
    net = mlp(2, 3, hidden=(48, 48))
    pool = np.random.default_rng(seed + 7).uniform(-3, 3, (3000, 2)) \
        .astype(np.float32)
    results = {}
    # --- dataset size sweep (Fig 6b)
    for frac in (0.01, 0.1, 1.0):
        src = UnlabeledDataset(pool[: max(int(len(pool) * frac), 8)])
        cfg = fl_cfg("feddf", rounds, seed=seed)
        res = run_federated(net, train, parts, val, test, cfg, source=src)
        results[f"size={frac}"] = res.best_acc
    # --- distillation steps sweep (Fig 6c)
    for steps in (20, 100, 400):
        cfg = fl_cfg("feddf", rounds, seed=seed, fusion=fusion_cfg(steps))
        res = run_federated(net, train, parts, val, test, cfg,
                            source=UnlabeledDataset(pool))
        results[f"steps={steps}"] = res.best_acc
    dt = time.time() - t0
    claims = {
        "one_percent_data_works":
            results["size=0.01"] >= results["size=1.0"] - 0.05,
        "moderate_steps_suffice":
            results["steps=100"] >= results["steps=400"] - 0.04,
    }
    emit("fig6_distill_steps", dt, f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
