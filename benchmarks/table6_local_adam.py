"""Table 6 (Appendix C.4.1): impact of the LOCAL optimizer (SGD vs Adam).

Paper finding: Adam for local training can help FL at mild heterogeneity
(alpha=1) but its benefit vanishes at alpha=0.1, while FedDF's gain over
FedAvg is robust to the local-training scheme — the benefit is orthogonal
to local optimization quality.
"""
from __future__ import annotations

import time

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated


def run(seed: int = 0) -> dict:
    rounds = scale(4, 10)
    t0 = time.time()
    results = {}
    for alpha in (1.0, 0.1):
        train, val, test, parts, src = default_problem(seed=seed, alpha=alpha)
        for local_opt in ("sgd", "adam"):
            for strat, source in (("fedavg", None), ("feddf", src)):
                cfg = fl_cfg(strat, rounds, seed=seed,
                             local_optimizer=local_opt)
                net = mlp(2, 3, hidden=(64, 64))
                res = run_federated(net, train, parts, val, test, cfg,
                                    source=source)
                results[f"alpha={alpha}/{local_opt}/{strat}"] = {
                    "best_acc": res.best_acc, "final_acc": res.final_acc}
    dt = time.time() - t0

    def best(k):
        return results[k]["best_acc"]

    claims = {
        # FedDF >= FedAvg under BOTH local optimizers at high heterogeneity
        "feddf_robust_to_local_opt_noniid": (
            best("alpha=0.1/sgd/feddf") >= best("alpha=0.1/sgd/fedavg") - 0.01
            and best("alpha=0.1/adam/feddf")
            >= best("alpha=0.1/adam/fedavg") - 0.01),
        # local Adam is not a substitute for better fusion at alpha=0.1
        # (paper: "the benefit vanishes with higher data heterogeneity")
        "feddf_sgd_beats_fedavg_adam_noniid": (
            best("alpha=0.1/sgd/feddf")
            >= best("alpha=0.1/adam/fedavg") - 0.01),
    }
    emit("table6_local_adam", dt, f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
