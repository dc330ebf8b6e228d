"""Table 5 (Appendix C.4.1): initialising the distillation student from the
round's weighted parameter AVERAGE beats initialising from the previous
round's fused model."""
from __future__ import annotations

import time

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated


def run(seed: int = 0) -> dict:
    rounds = scale(5, 12)
    t0 = time.time()
    train, val, test, parts, src = default_problem(seed=seed, alpha=0.3)
    net = mlp(2, 3, hidden=(48, 48))
    results = {}
    for init in ("average", "previous"):
        cfg = fl_cfg("feddf", rounds, seed=seed, feddf_init_from=init)
        res = run_federated(net, train, parts, val, test, cfg, source=src)
        results[init] = {"best_acc": res.best_acc,
                         "final_acc": res.final_acc}
    dt = time.time() - t0
    claims = {
        "average_init_wins": results["average"]["best_acc"]
        >= results["previous"]["best_acc"] - 0.01,
    }
    emit("table5_init_ablation", dt, f"claims_ok={sum(claims.values())}/1",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
