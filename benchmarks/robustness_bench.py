"""Robust fusion under byzantine uploads (ISSUE 8 acceptance).

Three runs of the same fedavg problem with ``f`` byzantine clients
(persistent sign-flip at 10x scale, ``FaultModel`` injection):

  * **undefended** — plain fedavg, screening and teacher filtering off:
    the attacker's uploads fuse straight into the global, measuring the
    raw damage;
  * **screened** — the default defense stack (delta-norm robust-z
    screening + quarantine), plain fedavg aggregation;
  * **robust_agg** — screening off but ``trimmed_mean`` aggregation
    (trim_frac sized to f), measuring what coordinate-wise trimming
    alone buys.

A fault-free fedavg run anchors the comparison; recorded per arm is
the final accuracy and its drift vs fault-free.  Also measured and
gated: the *validation overhead* — a vanishing injection rate turns
the full screening pipeline on without any fault ever firing, which
must cost <= 5% wall time over the plain config (min-of-3 walls both
sides) and reproduce its trajectory bitwise (asserted).

Writes ``BENCH_robustness.json`` (override with ``BENCH_ROBUSTNESS_OUT``).
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks.common import emit, scale
from benchmarks.timing import finish_bench
from repro.core import FLConfig, FusionConfig, mlp, run_rounds
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)
from repro.population import FaultConfig

K = 10
DIM, CLASSES = 16, 10
OUT = os.environ.get("BENCH_ROBUSTNESS_OUT", "BENCH_robustness.json")

CHAOS = dict(byzantine_frac=0.2, byzantine_scale=10.0,
             byzantine_mode="sign_flip", nan_rate=0.05)


def _problem(seed=0):
    ds = gaussian_mixture(4000, n_classes=CLASSES, dim=DIM, seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, K, 1.0, seed=seed)
    src = UnlabeledDataset(np.random.default_rng(seed + 1).uniform(
        -3, 3, (2048, DIM)).astype(np.float32))
    return train, val, test, parts, src


def _config(rounds, strategy="fedavg", **kw):
    return FLConfig(strategy=strategy, rounds=rounds, client_fraction=1.0,
                    local_epochs=10, local_batch_size=32, local_lr=0.05,
                    seed=0, fusion=FusionConfig(max_steps=200, patience=200,
                                                eval_every=50,
                                                batch_size=64), **kw)


def run() -> None:
    rounds = scale(10, 16)
    train, val, test, parts, src = _problem()
    net = mlp(DIM, CLASSES, hidden=(128, 128))

    def one(cfg):
        t0 = time.perf_counter()
        results, globals_, _ = run_rounds(
            [net], [0] * K, train, parts, val, test, cfg,
            source=src, driver="sync")
        jax.block_until_ready(jax.tree.leaves(globals_[0])[0])
        wall = time.perf_counter() - t0
        logs = results[0].logs
        finite = all(bool(np.isfinite(np.asarray(l)).all())
                     for l in jax.tree.leaves(globals_[0]))
        return {"final_acc": results[0].final_acc, "wall_s": wall,
                "finite": finite,
                "quarantined": sum(l.n_quarantined for l in logs),
                "corrupted": sum(l.n_corrupted for l in logs)}, results[0]

    clean, r_clean = one(_config(rounds))

    # armed-and-screening: a vanishing injection rate keeps every fault
    # draw silent but turns the validation pipeline ON — delta-norm
    # screening + the divergence guard run every round against honest
    # uploads.  The trajectory is asserted bitwise (an honest cohort
    # never trips the robust-z screen); the wall overhead is min-of-3
    # on both sides so jit warmup and scheduler noise cancel.
    armed_cfg = _config(rounds, faults=FaultConfig(
        nan_rate=1e-12, screen="on", quorum=0.8, retries=3))
    walls_plain, walls_armed = [], []
    r_armed = None
    for _ in range(3):
        c2, _ = one(_config(rounds))
        walls_plain.append(c2["wall_s"])
        a2, r_armed = one(armed_cfg)
        walls_armed.append(a2["wall_s"])
    assert [l.test_acc for l in r_armed.logs] == \
        [l.test_acc for l in r_clean.logs], \
        "armed screening on honest uploads must not perturb the trajectory"
    overhead = min(walls_armed) / min(walls_plain) - 1.0

    undefended, _ = one(_config(rounds, faults=FaultConfig(
        **CHAOS, screen="off", teacher_filter="off")))
    screened, _ = one(_config(rounds, faults=FaultConfig(**CHAOS)))
    # trim sized to the threat: byzantine_frac 0.2 of K=10 realizes 2
    # attackers at this seed; trim_frac 0.35 -> trim 3 per side leaves
    # room for an occasional unscreened NaN row in the same tail
    robust, _ = one(_config(rounds, strategy="trimmed_mean",
                            trim_frac=0.35,
                            faults=FaultConfig(**CHAOS, screen="off",
                                               teacher_filter="off")))

    drift = lambda arm: arm["final_acc"] - clean["final_acc"]
    rec = {
        "K": K, "dim": DIM, "classes": CLASSES, "rounds": rounds,
        "chaos": CHAOS,
        "clean": clean,
        "idle_overhead_frac": overhead,
        "undefended": {**undefended, "drift": drift(undefended)},
        "screened": {**screened, "drift": drift(screened)},
        "trimmed_mean": {**robust, "drift": drift(robust)},
    }
    emit("robustness_screened_drift", abs(drift(screened)) * 1e6,
         f"undef_drift_{drift(undefended):.3f}", record=rec)
    finish_bench("robustness", rec, out=OUT,
                 config={"K": K, "rounds": rounds, "chaos": CHAOS})
    print(f"wrote {OUT}: clean {clean['final_acc']:.4f}, undefended "
          f"{undefended['final_acc']:.4f} (drift {drift(undefended):+.4f}), "
          f"screened {screened['final_acc']:.4f} "
          f"(drift {drift(screened):+.4f}, quarantined "
          f"{screened['quarantined']}), trimmed_mean "
          f"{robust['final_acc']:.4f} (drift {drift(robust):+.4f}); "
          f"idle fault-seam overhead {overhead * 100:+.1f}%")


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
