"""Figure 4: heterogeneous systems — three distinct prototypes
(ResNet-20/32/ShuffleNetV2 analogue: different widths/depths).  FedDF
dominates per-group FedAvg each round, with the ensemble as upper bound."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated_heterogeneous


def run(seed: int = 0) -> dict:
    rounds = scale(4, 10)
    t0 = time.time()
    train, val, test, parts, src = default_problem(seed=seed, alpha=1.0,
                                                   n_clients=9)
    nets = [mlp(2, 3, hidden=(32, 32), name="proto-s"),
            mlp(2, 3, hidden=(64, 64), name="proto-m"),
            mlp(2, 3, hidden=(48, 48, 48), name="proto-d")]
    proto = [k % 3 for k in range(9)]
    results = {}
    for strat, source in (("fedavg", None), ("feddf", src)):
        cfg = fl_cfg(strat, rounds, seed=seed, client_fraction=0.67)
        res, _ = run_federated_heterogeneous(nets, proto, train, parts, val,
                                             test, cfg, source=source)
        for g, r in enumerate(res):
            results[f"{strat}/proto{g}"] = {
                "per_round": [l.test_acc for l in r.logs],
                "best": r.best_acc,
                "ensemble": [l.ensemble_acc for l in r.logs]}
    dt = time.time() - t0
    feddf_mean = np.mean([results[f"feddf/proto{g}"]["best"]
                          for g in range(3)])
    fedavg_mean = np.mean([results[f"fedavg/proto{g}"]["best"]
                           for g in range(3)])
    ens = max(results["feddf/proto0"]["ensemble"])
    claims = {
        "feddf_dominates_groupwise_fedavg": feddf_mean >= fedavg_mean - 0.01,
        "ensemble_is_upper_bound":
            ens >= max(results[f"feddf/proto{g}"]["best"]
                       for g in range(3)) - 0.03,
    }
    emit("fig4_heterogeneous", dt, f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims,
          "feddf_mean": float(feddf_mean), "fedavg_mean": float(fedavg_mean)})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
