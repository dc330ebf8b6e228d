"""Table 4: federated learning with 1-bit binarized clients (STE local
training).  Paper: FedDF matches/bests FedAvg on binarized ResNet-8 without
GN tuning, at ~1/10 the uplink bytes."""
from __future__ import annotations

import time

import jax

from benchmarks.common import default_problem, emit, fl_cfg, scale
from repro.core import mlp, run_federated
from repro.core.quantize import binarize, comm_bytes


def run(seed: int = 0) -> dict:
    rounds = scale(6, 15)
    t0 = time.time()
    train, val, test, parts, src = default_problem(seed=seed, alpha=1.0)
    net = mlp(2, 3, hidden=(48, 48), norm="none")
    results = {}
    for name, (strat, source) in {
        "fedavg_binary": ("fedavg", None),
        "feddf_binary": ("feddf", src),
    }.items():
        cfg = fl_cfg(strat, rounds, seed=seed, quantize=binarize,
                     local_lr=0.1)
        res = run_federated(net, train, parts, val, test, cfg, source=source)
        results[name] = {"best_acc": res.best_acc,
                         "final_acc": res.final_acc}
    p0 = net.init(jax.random.PRNGKey(0))
    results["uplink_bytes_fp32"] = comm_bytes(p0)
    results["uplink_bytes_binary"] = comm_bytes(p0, binarized=True)
    dt = time.time() - t0
    claims = {
        "feddf_binary_at_least_fedavg":
            results["feddf_binary"]["best_acc"]
            >= results["fedavg_binary"]["best_acc"] - 0.02,
        "binary_compression_over_8x":
            results["uplink_bytes_fp32"]
            > 8 * results["uplink_bytes_binary"],
    }
    emit("table4_lowbit", dt, f"claims_ok={sum(claims.values())}/2",
         {"results": results, "claims": claims})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
