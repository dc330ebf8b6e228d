"""Figure 5: robustness to the distillation data source — out-of-domain
unlabeled data ≈ generator >> random noise (abrupt decline on a
'dramatically different manifold')."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated
from repro.data import (GeneratorSource, RandomNoiseSource, UnlabeledDataset)


def run(seed: int = 0) -> dict:
    rounds = scale(5, 12)
    t0 = time.time()
    train, val, test, parts, _ = default_problem(seed=seed, alpha=1.0)
    net = mlp(2, 3, hidden=(48, 48))
    # in-domain unlabeled, out-of-domain unlabeled, frozen generator, noise
    sources = {
        "in_domain": UnlabeledDataset(train.x),
        "out_of_domain": UnlabeledDataset(
            np.random.default_rng(seed + 7).uniform(-3, 3, (3000, 2))
            .astype(np.float32)),
        "generator": GeneratorSource((2,), mean=0.0, std=2.0, seed=seed),
        # noise from a *wildly* different manifold (tiny range — off-support)
        "noise_offmanifold": RandomNoiseSource((2,), low=50.0, high=60.0),
    }
    results = {}
    for name, src in sources.items():
        cfg = fl_cfg("feddf", rounds, seed=seed)
        res = run_federated(net, train, parts, val, test, cfg, source=src)
        results[name] = res.best_acc
    dt = time.time() - t0
    claims = {
        "generator_close_to_unlabeled":
            results["generator"] >= results["out_of_domain"] - 0.06,
        "offmanifold_noise_declines":
            results["noise_offmanifold"] <= results["out_of_domain"] + 0.02,
        "in_domain_best_or_close":
            results["in_domain"] >= results["out_of_domain"] - 0.03,
    }
    emit("fig5_distill_sources", dt, f"claims_ok={sum(claims.values())}/3",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
