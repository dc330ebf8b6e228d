"""Round-driver benchmark (ISSUE 4 acceptance).

Wall-clock ROUND throughput of the async-pipelined driver against the
serial sync driver on the homogeneous K=8 toy config — the pipeline
dispatches round t+1's batched client training while round t's
FedDF/logit-bank fusion runs, so the client phase hides inside the
fusion phase (docs/drivers.md).  The config balances the two phases the
way the paper's real workloads are balanced (local training comparable
to server distillation); throughput is MARGINAL between a short and a
long run of the same config (min over reps each), so the per-run jit
compiles cancel in the difference — the distill_bench idiom.

Also recorded: the async(staleness=0) run, which must reproduce the sync
per-round accuracy log EXACTLY (the bench asserts it — prefetch alone
never changes the trajectory), and the staleness=1 final-accuracy drift.

Writes ``BENCH_driver.json`` (override with ``BENCH_DRIVER_OUT``) so
CI's driver-smoke job records the perf trajectory; emits the usual CSV
lines via ``benchmarks.common.emit``.  Timing idioms live in
``benchmarks/timing.py`` (shared with ``round_engine_bench``).
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from benchmarks.common import emit, scale
from benchmarks.timing import finish_bench, marginal_rate
from repro.core import FLConfig, FusionConfig, mlp, run_rounds
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)
from repro.drivers import make_driver

K = 8
DIM, CLASSES = 16, 10
POOL_N = 2048
OUT = os.environ.get("BENCH_DRIVER_OUT", "BENCH_driver.json")


def _problem(seed=0):
    ds = gaussian_mixture(4000, n_classes=CLASSES, dim=DIM, seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, K, 1.0, seed=seed)
    src = UnlabeledDataset(np.random.default_rng(seed + 1).uniform(
        -3, 3, (POOL_N, DIM)).astype(np.float32))
    return train, val, test, parts, src


def _config(rounds, steps):
    # local training and fusion deliberately comparable: that is the
    # regime the pipeline targets (client phase hides inside fusion)
    return FLConfig(
        strategy="feddf", rounds=rounds, client_fraction=1.0,
        local_epochs=25, local_batch_size=32, local_lr=0.05, seed=0,
        fusion=FusionConfig(max_steps=steps, patience=10 * steps,
                            eval_every=100, batch_size=128,
                            use_fused_kernel=False))


def run() -> None:
    r_short = 2
    r_long = scale(5, 8)
    steps = scale(300, 400)
    train, val, test, parts, src = _problem()
    net = mlp(DIM, CLASSES, hidden=(128, 128))

    def measure(driver_fn):
        # each run_rounds builds a fresh engine (fresh client-update jit);
        # marginal_rate's short-vs-long difference cancels the identical
        # compile cost, leaving the steady-state round throughput
        def one_run(rounds):
            cfg = _config(rounds, steps)
            results, globals_, _ = run_rounds(
                [net], [0] * K, train, parts, val, test, cfg,
                source=src, driver=driver_fn())
            jax.block_until_ready(jax.tree.leaves(globals_[0])[0])
            return results[0]

        stats, result = marginal_rate(one_run, r_short, r_long, reps=2)
        return {"wall_short_s": stats["wall_short_s"],
                "wall_long_s": stats["wall_long_s"],
                "rounds_per_s": stats["per_s"],
                "final_acc": result.final_acc}, result

    sync, r_sync = measure(lambda: "sync")
    async0, r_async0 = measure(
        lambda: make_driver("async_pipelined", staleness=0, prefetch=2))
    async1, r_async = measure(
        lambda: make_driver("async_pipelined", staleness=1, prefetch=2))

    assert [l.test_acc for l in r_async0.logs] == \
        [l.test_acc for l in r_sync.logs], \
        "async(staleness=0) must reproduce the sync trajectory exactly"
    async0["trajectory_equal"] = True

    speedup = async1["rounds_per_s"] / sync["rounds_per_s"]
    drift = abs(r_sync.final_acc - r_async.final_acc)
    rec = {
        "K": K, "dim": DIM, "classes": CLASSES, "hidden": [128, 128],
        "rounds_short": r_short, "rounds_long": r_long,
        "local_epochs": 25, "distill_steps": steps, "distill_batch": 128,
        "sync": sync, "async_staleness0": async0,
        "async_staleness1": async1,
        "speedup": speedup,
        "final_acc_drift": drift,
    }
    emit("driver_round_throughput", 1.0 / async1["rounds_per_s"],
         f"speedup_x{speedup:.2f}", record=rec)
    finish_bench("driver", rec, out=OUT,
                 config={"K": K, "dim": DIM, "classes": CLASSES,
                         "rounds_short": r_short, "rounds_long": r_long})
    print(f"wrote {OUT}: async_pipelined(staleness=1) x{speedup:.2f} over "
          f"sync ({sync['rounds_per_s']:.2f} -> "
          f"{async1['rounds_per_s']:.2f} rounds/s marginal), "
          f"final-acc drift {drift:.4f}")


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
