"""Table 1: communication rounds to reach target accuracy — FedAvg vs
FedProx vs FedAvgM vs FedDF under non-iid local data (Dirichlet alpha).

Paper claim (CIFAR-10/ResNet-8): FedDF needs significantly fewer rounds in
every scenario and is markedly more robust to data heterogeneity (FedAvg's
round curve oscillates; FedDF's is stable).

Offline stand-in: 5-class, 8-d Gaussian mixture with class overlap; 10
clients, C=0.4, 20 local epochs.  Rounds-to-target is computed post hoc
from the full round curve (no early stop), averaged over seeds.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, fl_cfg, fusion_cfg, scale
from repro.core import FLConfig, mlp, run_federated
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)

STRATS = ("fedavg", "fedprox", "fedavgm", "feddf")


def _problem(alpha, seed):
    ds = gaussian_mixture(4000, n_classes=5, dim=8, spread=2.4, noise=1.1,
                          seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, 10, alpha, seed=seed)
    src = UnlabeledDataset(np.random.default_rng(seed + 7).uniform(
        -4, 4, (3000, 8)).astype(np.float32))
    return train, val, test, parts, src


def _r2t(curve, target):
    for i, acc in enumerate(curve, start=1):
        if acc >= target:
            return i
    return None


def run(seed: int = 0) -> dict:
    rounds = scale(10, 20)
    n_seeds = scale(2, 3)
    target = 0.65
    t0 = time.time()
    results = {}
    for alpha in (1.0, 0.1):
        for strat in STRATS:
            curves, r2ts, bests, tails = [], [], [], []
            for s in range(n_seeds):
                train, val, test, parts, src = _problem(alpha, seed + s)
                net = mlp(8, 5, hidden=(48, 48))
                cfg = fl_cfg(strat, rounds, seed=seed + s,
                             local_batch_size=32)
                res = run_federated(net, train, parts, val, test, cfg,
                                    source=src if strat == "feddf" else None)
                curve = [l.test_acc for l in res.logs]
                curves.append(curve)
                r2ts.append(_r2t(curve, target))
                bests.append(res.best_acc)
                tails.append(float(np.mean(curve[rounds // 2:])))
            r2t_num = [r if r is not None else rounds + 5 for r in r2ts]
            results[f"alpha={alpha}/{strat}"] = {
                "rounds_to_target": r2ts,
                "mean_r2t_capped": float(np.mean(r2t_num)),
                "best_acc": float(np.mean(bests)),
                "tail_mean_acc": float(np.mean(tails)),
                "curves": curves,
            }
    dt = time.time() - t0

    def g(alpha, strat, key):
        return results[f"alpha={alpha}/{strat}"][key]

    claims = {
        # FedDF reaches target in no more rounds than the best baseline (iid-ish)
        "feddf_competitive_r2t_iid":
            g(1.0, "feddf", "mean_r2t_capped")
            <= min(g(1.0, s, "mean_r2t_capped")
                   for s in STRATS[:3]) + 1.0,
        "feddf_fewer_rounds_noniid":
            g(0.1, "feddf", "mean_r2t_capped")
            <= g(0.1, "fedavg", "mean_r2t_capped"),
        # stability: FedDF's late-round accuracy >= baselines' under non-iid
        "feddf_stable_noniid":
            g(0.1, "feddf", "tail_mean_acc")
            >= max(g(0.1, s, "tail_mean_acc") for s in STRATS[:3]) - 0.015,
    }
    emit("table1_rounds_to_target", dt,
         f"claims_ok={sum(claims.values())}/3",
         {"results": results, "claims": claims, "target": target})
    return {"results": results, "claims": claims}


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
