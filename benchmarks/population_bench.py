"""Population / buffered-async benchmark (ISSUE 7 acceptance).

Marginal UPLOAD throughput (uploads fused per second) of the
``buffered_async`` driver under a realistic traffic model against the
serial ``sync`` driver on the homogeneous K=8 toy config.  The buffered
driver's gain is FedBuff's amortization knob: the server fuses every
``M = buffer_size`` buffered uploads, so with M = 3K three client waves
share ONE ensemble-distillation fusion — the per-round server cost the
sync loop pays per K uploads — while waves train concurrently with the
previous fusion on a worker thread and stragglers fuse late with
``(1+s)^-a`` importance instead of gating the round.  Throughput is
MARGINAL between a short and a long run of the same config (min over
reps each), so per-run jit compiles cancel — the ``distill_bench``
idiom shared via ``benchmarks/timing.py``.

Also asserted, not just recorded: the DEGENERATE buffered config
(``buffer_size == K``, zero latency, uniform sampler, ``staleness=0``)
reproduces the sync per-round accuracy log exactly — the population
seam costs nothing when unused.  The traffic run's final-accuracy drift
vs sync is recorded and gated <= 0.5pt in CI.

Writes ``BENCH_population.json`` (override with ``BENCH_POPULATION_OUT``).
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
from repro.population import PopulationConfig, TrafficConfig

K = 8
DIM, CLASSES = 16, 10
POOL_N = 2048
OUT = os.environ.get("BENCH_POPULATION_OUT", "BENCH_population.json")

# the traffic regime the subsystem exists for: a quarter of the
# population uploads 8x slower, uploads jitter lognormally, a little
# dropout; max_staleness is generous so stragglers fuse downweighted
# instead of being discarded
TRAFFIC = TrafficConfig(arrival="bernoulli", rate=0.95, latency=1.0,
                        jitter=0.3, straggler_frac=0.25,
                        straggler_mult=8.0, dropout=0.02)


def _problem(seed=0):
    ds = gaussian_mixture(4000, n_classes=CLASSES, dim=DIM, seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, K, 1.0, seed=seed)
    src = UnlabeledDataset(np.random.default_rng(seed + 1).uniform(
        -3, 3, (POOL_N, DIM)).astype(np.float32))
    return train, val, test, parts, src


def _config(rounds, steps, population=None):
    # local training and fusion deliberately comparable: the buffered
    # driver hides wave training inside the previous round's fusion
    return FLConfig(
        strategy="feddf", rounds=rounds, client_fraction=1.0,
        local_epochs=25, local_batch_size=32, local_lr=0.05, seed=0,
        fusion=FusionConfig(max_steps=steps, patience=10 * steps,
                            eval_every=100, batch_size=128,
                            use_fused_kernel=False),
        population=population or PopulationConfig())


def run() -> None:
    r_short = 2
    r_long = scale(5, 8)
    steps = scale(500, 700)
    train, val, test, parts, src = _problem()
    net = mlp(DIM, CLASSES, hidden=(128, 128))

    def measure(driver_fn, population=None, uploads_per_round=K):
        def one_run(rounds):
            cfg = _config(rounds, steps, population)
            results, globals_, _ = run_rounds(
                [net], [0] * K, train, parts, val, test, cfg,
                source=src, driver=driver_fn())
            jax.block_until_ready(jax.tree.leaves(globals_[0])[0])
            return results[0]

        stats, result = marginal_rate(one_run, r_short, r_long, reps=2)
        return {"wall_short_s": stats["wall_short_s"],
                "wall_long_s": stats["wall_long_s"],
                "rounds_per_s": stats["per_s"],
                "uploads_per_s": stats["per_s"] * uploads_per_round,
                "final_acc": result.final_acc}, result

    sync, r_sync = measure(lambda: "sync")

    # degenerate buffered == sync, asserted bitwise on the accuracy log
    degen, r_degen = measure(
        lambda: make_driver("buffered_async", staleness=0))
    assert [l.test_acc for l in r_degen.logs] == \
        [l.test_acc for l in r_sync.logs], \
        "degenerate buffered_async must reproduce the sync trajectory"
    degen["trajectory_equal"] = True

    # M = 3K: three waves of client training per server fusion — the
    # FedBuff amortization the uploads/s ratio quantifies
    pop = PopulationConfig(size=4 * K, sampler="prioritized",
                           buffer_size=3 * K, max_staleness=8,
                           staleness_exponent=0.5, traffic=TRAFFIC)
    buf, r_buf = measure(
        lambda: make_driver("buffered_async", staleness=1),
        population=pop, uploads_per_round=3 * K)

    ratio = buf["uploads_per_s"] / sync["uploads_per_s"]
    drift = abs(r_sync.final_acc - r_buf.final_acc)
    mean_staleness = float(np.mean([
        sum(s * c for s, c in enumerate(l.staleness_hist)) /
        max(sum(l.staleness_hist), 1)
        for l in r_buf.logs if l.staleness_hist is not None]))
    rec = {
        "K": K, "dim": DIM, "classes": CLASSES, "hidden": [128, 128],
        "rounds_short": r_short, "rounds_long": r_long,
        "local_epochs": 25, "distill_steps": steps, "distill_batch": 128,
        "population_size": pop.size, "buffer_size": pop.buffer_size,
        "traffic": TRAFFIC.__dict__,
        "sync": sync, "buffered_degenerate": degen,
        "buffered_traffic": buf,
        "uploads_ratio": ratio,
        "final_acc_drift": drift,
        "mean_staleness": mean_staleness,
    }
    emit("population_upload_throughput", 1.0 / buf["uploads_per_s"],
         f"uploads_x{ratio:.2f}", record=rec)
    finish_bench("population", rec, out=OUT,
                 config={"K": K, "population_size": pop.size,
                         "buffer_size": pop.buffer_size,
                         "rounds_short": r_short, "rounds_long": r_long})
    print(f"wrote {OUT}: buffered_async(traffic) x{ratio:.2f} uploads/s "
          f"over sync ({sync['uploads_per_s']:.2f} -> "
          f"{buf['uploads_per_s']:.2f}), final-acc drift {drift:.4f}, "
          f"mean staleness {mean_staleness:.2f}")


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
