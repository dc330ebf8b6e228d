"""Table 3: unnormalised nets (VGG-analogue) destabilise under non-iid
local training; drop-worst rescues aggregation; FedDF tops FedAvg/FedProx.

We provoke instability with a deeper norm-free MLP and a hot learning rate,
then compare aggregation with and without drop-worst."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated


def run(seed: int = 0) -> dict:
    rounds = scale(6, 15)
    t0 = time.time()
    train, val, test, parts, src = default_problem(seed=seed, alpha=0.3,
                                                   n=4000)
    net = mlp(2, 3, hidden=(64, 64, 64, 64), norm="none")
    results = {}
    for name, (strat, dw, source) in {
        "fedavg_no_dropworst": ("fedavg", False, None),
        "fedavg": ("fedavg", True, None),
        "fedprox": ("fedprox", True, None),
        "feddf": ("feddf", True, src),
    }.items():
        accs = []
        for s in range(scale(2, 3)):
            cfg = fl_cfg(strat, rounds, seed=seed + s, drop_worst=dw,
                         local_lr=0.2)  # hot lr -> occasional divergence
            res = run_federated(net, train, parts, val, test, cfg,
                                source=source)
            accs.append(res.best_acc)
        results[name] = {"mean": float(np.mean(accs)),
                         "std": float(np.std(accs)), "accs": accs}
    dt = time.time() - t0
    claims = {
        "dropworst_stabilises":
            results["fedavg"]["mean"] >=
            results["fedavg_no_dropworst"]["mean"] - 0.01,
        "feddf_top":
            results["feddf"]["mean"] >= max(
                results["fedavg"]["mean"], results["fedprox"]["mean"]) - 0.02,
    }
    emit("table3_dropworst", dt, f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
