"""Table 2: the BatchNorm non-iid 'quagmire' — FedAvg+BN degrades under
non-iid data; GN alleviates it; FedDF+BN beats both without touching the
architecture."""
from __future__ import annotations

import time

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated


def run(seed: int = 0) -> dict:
    rounds = scale(6, 15)
    results = {}
    t0 = time.time()
    for alpha in (1.0, 0.1):
        train, val, test, parts, src = default_problem(seed=seed, alpha=alpha,
                                                       n=4000)
        cases = {
            "fedavg_bn": ("fedavg", "bn", None),
            "fedavg_gn": ("fedavg", "gn", None),
            "fedprox_gn": ("fedprox", "gn", None),
            "fedavgm_gn": ("fedavgm", "gn", None),
            "feddf_bn": ("feddf", "bn", src),
        }
        for name, (strat, norm, source) in cases.items():
            net = mlp(2, 3, hidden=(48, 48), norm=norm)
            res = run_federated(net, train, parts, val, test,
                                fl_cfg(strat, rounds, seed=seed),
                                source=source)
            results[f"alpha={alpha}/{name}"] = {
                "best_acc": res.best_acc, "final_acc": res.final_acc}
    dt = time.time() - t0
    claims = {
        # FedDF w/ BN >= FedAvg w/ BN under non-iid (paper: +9 pts)
        "feddf_bn_beats_fedavg_bn_noniid":
            results["alpha=0.1/feddf_bn"]["best_acc"]
            >= results["alpha=0.1/fedavg_bn"]["best_acc"] - 0.01,
        # FedDF w/ BN >= GN-repaired baselines (paper: +3 pts)
        "feddf_bn_beats_gn_baselines_noniid":
            results["alpha=0.1/feddf_bn"]["best_acc"]
            >= max(results["alpha=0.1/fedavg_gn"]["best_acc"],
                   results["alpha=0.1/fedavgm_gn"]["best_acc"]) - 0.02,
    }
    emit("table2_normalization", dt, f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
