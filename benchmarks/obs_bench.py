"""Flight-recorder overhead benchmark (observability acceptance).

Armed-vs-disarmed wall clock of the sync toy config: the ISSUE budget
is <= 2% wall overhead with tracing armed, and EXACT bit-identity of
the trajectory (disarmed spans are one ``is None`` check per phase, so
disarmed must be free; armed appends one JSONL record per span).

The measurement is built for a tight 2% gate: the true recorder cost
(~6 span records/round, ~100us) is far below run-to-run CPU noise on a
short run, so instead of the short-vs-long marginal idiom (whose
subtraction AMPLIFIES noise) this bench times LONG runs — the per-run
jit compile amortizes to a few percent of wall, diluting the ratio far
less than noise would corrupt a marginal — interleaving disarmed/armed
pairs so load drift hits both arms alike, and takes the min wall per
arm over reps.  Both gates are asserted in-bench AND re-checked by
``benchmarks/check_history.py`` from the history record.

Also recorded: the armed run's per-phase wall breakdown
(``recorder().summary()`` — train/aggregate/eval per round), which is
the artifact CI surfaces for "where did this round's time go".

Writes ``BENCH_obs.json`` (override with ``BENCH_OBS_OUT``) and appends
the schema'd record to ``BENCH_history.jsonl``.
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit, scale
from benchmarks.timing import finish_bench
from repro.core import FLConfig, mlp, run_rounds
from repro.data import (dirichlet_partition, gaussian_mixture,
                        train_val_test_split)
from repro.drivers import make_driver
from repro.obs import trace

K = 8
DIM, CLASSES = 16, 10
OUT = os.environ.get("BENCH_OBS_OUT", "BENCH_obs.json")


def _problem(seed=0):
    ds = gaussian_mixture(4000, n_classes=CLASSES, dim=DIM, seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, K, 1.0, seed=seed)
    return train, val, test, parts


def _config(rounds):
    return FLConfig(strategy="fedavg", rounds=rounds, client_fraction=1.0,
                    local_epochs=25, local_batch_size=32, local_lr=0.05,
                    seed=0)


def run() -> None:
    rounds = scale(20, 40)
    reps = 4
    train, val, test, parts = _problem()
    net = mlp(DIM, CLASSES, hidden=(64, 64))
    tmp = tempfile.mkdtemp(prefix="obs_bench_")

    summary = {}

    def one_run(armed, rep):
        if armed:
            trace.arm(path=os.path.join(tmp, f"spans_rep{rep}.jsonl"))
        try:
            t0 = time.time()
            results, globals_, _ = run_rounds(
                [net], [0] * K, train, parts, val, test,
                _config(rounds), driver=make_driver("sync"))
            jax.block_until_ready(jax.tree.leaves(globals_[0])[0])
            wall = time.time() - t0
            if armed:
                summary.update(trace.recorder().summary())
        finally:
            if armed:
                trace.disarm()
        return wall, results[0]

    walls = {False: [], True: []}
    r_off = r_on = None
    for rep in range(reps):  # interleaved: load drift hits both arms
        w, r_off = one_run(False, rep)
        walls[False].append(w)
        w, r_on = one_run(True, rep)
        walls[True].append(w)

    trajectory_equal = (
        [l.test_acc for l in r_on.logs] == [l.test_acc for l in r_off.logs])
    assert trajectory_equal, \
        "armed flight recorder must not perturb the trajectory"

    overhead = min(walls[True]) / min(walls[False]) - 1.0
    rec = {
        "K": K, "dim": DIM, "classes": CLASSES, "hidden": [64, 64],
        "rounds": rounds, "reps": reps, "local_epochs": 25,
        "disarmed": {"wall_s": min(walls[False]),
                     "rounds_per_s": rounds / min(walls[False])},
        "armed": {"wall_s": min(walls[True]),
                  "rounds_per_s": rounds / min(walls[True])},
        "overhead_frac": overhead,
        "trajectory_equal": trajectory_equal,
        "phase_totals_s": summary.get("phase_totals_s", {}),
        "idle_gap_s": summary.get("idle_gap_s", 0.0),
        "per_round": summary.get("per_round", {}),
    }
    assert overhead <= 0.02, \
        f"armed flight-recorder overhead {overhead:.4f} > 2%"
    emit("obs_recorder_overhead", min(walls[True]) / rounds,
         f"overhead_{overhead * 100:+.2f}%", record=rec)
    finish_bench("obs", rec, out=OUT,
                 config={"K": K, "rounds": rounds, "reps": reps})
    print(f"wrote {OUT}: armed {min(walls[True]):.2f}s vs disarmed "
          f"{min(walls[False]):.2f}s over {rounds} rounds "
          f"(overhead {overhead * 100:+.2f}%), trajectory_equal="
          f"{trajectory_equal}")


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
