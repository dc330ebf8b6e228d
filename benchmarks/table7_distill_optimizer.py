"""Table 7 (Appendix C.4.1): the SERVER distillation optimizer.

Paper finding (CIFAR-10/ResNet-8): SGD-distillation underperforms
(76.68 vs Adam's 80.27 at alpha=1); SWAG-sampled extra teachers
(FedDistill [10]) perform on par with plain Adam (80.84 vs 80.27) at the
cost of two extra hyperparameters — justifying FedDF's default choice.
"""
from __future__ import annotations

import dataclasses
import time

from benchmarks.common import default_problem, emit, fl_cfg, fusion_cfg, scale
from repro.core import mlp, run_federated


def run(seed: int = 0) -> dict:
    rounds = scale(4, 10)
    t0 = time.time()
    train, val, test, parts, src = default_problem(seed=seed, alpha=1.0)
    results = {}
    variants = {
        "sgd": dict(optimizer="sgd", lr=0.05),
        "adam": dict(optimizer="adam"),
        "swag": dict(optimizer="adam", swag_samples=5, swag_scale=0.5),
    }
    for name, fkw in variants.items():
        cfg = fl_cfg("feddf", rounds, seed=seed,
                     fusion=dataclasses.replace(fusion_cfg(), **fkw))
        net = mlp(2, 3, hidden=(64, 64))
        res = run_federated(net, train, parts, val, test, cfg, source=src)
        results[name] = {"best_acc": res.best_acc,
                         "final_acc": res.final_acc}
    dt = time.time() - t0
    claims = {
        # Adam >= SGD for the server-side ensemble distillation
        "adam_at_least_sgd": (results["adam"]["best_acc"]
                              >= results["sgd"]["best_acc"] - 0.01),
        # SWAG teachers are on par with plain Adam (paper: 80.84 vs 80.27)
        "swag_on_par_with_adam": (abs(results["swag"]["best_acc"]
                                      - results["adam"]["best_acc"]) <= 0.03),
    }
    emit("table7_distill_optimizer", dt,
         f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
