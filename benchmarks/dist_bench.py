"""Distributed runtime acceptance bench (ISSUE 10; docs/distributed.md).

Five cases over one fedavg problem, all through the ``distributed``
driver's loopback transport:

  * **degenerate** — loopback, fp32 codec, zero transport faults: must
    be bit-identical to the ``sync`` driver (trajectory and final
    globals);
  * **chaos (defended)** — one client pod killed mid-round plus 5%
    frame corruption under a 0.5 quorum: the defense ladder (CRC retry,
    deadline re-dispatch, heartbeat re-routing, quorum skip) must hold
    the final accuracy within 1pt of the clean run, with the telemetry
    (retries / deadline misses / pod death) proving the faults fired;
  * **undefended** — the same corruption at 30% with ``verify_crc``
    off: corrupted frames decode to garbage parameters and fuse, so the
    run must visibly degrade (that the *defended* arm doesn't is the
    point of the comparison);
  * **wire** — identical runs under the fp32 / int8 / binarize payload
    codecs, recording actual bytes-on-wire: int8 must cut uplink bytes
    >= 3x vs fp32 (~4x payload, minus frame overhead);
  * **restart** — a checkpointed run with a wire log, then a simulated
    fusion-pod crash + restart from the round-2 snapshot: the resumed
    round replays its uploads off the wire log (zero uplink bytes) and
    the trajectory matches the uninterrupted run exactly.

Writes ``BENCH_dist.json`` (override with ``BENCH_DIST_OUT``) plus one
schema'd ``BENCH_history.jsonl`` record gated by
``benchmarks/check_history.py --require dist``.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import emit, scale
from benchmarks.timing import finish_bench
from repro.core import FLConfig, FusionConfig, mlp, run_rounds
from repro.data import (dirichlet_partition, gaussian_mixture,
                        train_val_test_split)
from repro.dist.config import DistConfig
from repro.obs.metrics import REGISTRY
from repro.population import FaultConfig

K = 8
DIM, CLASSES = 16, 10
OUT = os.environ.get("BENCH_DIST_OUT", "BENCH_dist.json")


def _problem(seed=0):
    ds = gaussian_mixture(3000, n_classes=CLASSES, dim=DIM, seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, K, 1.0, seed=seed)
    return train, val, test, parts


def _config(rounds, dist=None, faults=None, **kw):
    return FLConfig(
        strategy="fedavg", rounds=rounds, client_fraction=0.5,
        local_epochs=10, local_batch_size=32, local_lr=0.05, seed=0,
        fusion=FusionConfig(max_steps=100, patience=100, eval_every=50,
                            batch_size=64),
        dist=dist if dist is not None else DistConfig(),
        faults=faults if faults is not None else FaultConfig(), **kw)


def run() -> None:
    rounds = scale(4, 8)
    train, val, test, parts = _problem()
    net = mlp(DIM, CLASSES, hidden=(64, 64))

    def one(cfg, driver, **rr_kw):
        t0 = time.perf_counter()
        results, globals_, _ = run_rounds(
            [net], [0] * K, train, parts, val, test, cfg, driver=driver,
            **rr_kw)
        jax.block_until_ready(jax.tree.leaves(globals_[0])[0])
        wall = time.perf_counter() - t0
        logs = results[0].logs
        finite = all(bool(np.isfinite(np.asarray(l)).all())
                     for l in jax.tree.leaves(globals_[0]))
        return {
            "final_acc": results[0].final_acc, "wall_s": wall,
            "finite": finite,
            "per_round": [l.test_acc for l in logs],
            "bytes_up": sum(l.wire_bytes_up for l in logs),
            "bytes_down": sum(l.wire_bytes_down for l in logs),
            "wire_retries": sum(l.n_wire_retries for l in logs),
            "crc_failures": sum(l.n_crc_failures for l in logs),
            "deadline_misses": sum(l.n_deadline_misses for l in logs),
            "wire_lost": sum(l.n_wire_lost for l in logs),
            "min_pods_alive": min((l.n_pods_alive for l in logs),
                                  default=0),
        }, results[0], globals_

    def same_globals(a, b):
        return all(bool((np.asarray(x) == np.asarray(y)).all())
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    # -- degenerate: loopback + fp32 + no faults == sync, bitwise --------
    sync_m, sync_r, sync_g = one(_config(rounds), "sync")
    dist_m, dist_r, dist_g = one(
        _config(rounds, dist=DistConfig(n_pods=2)), "distributed")
    degenerate = {
        "trajectory_equal": (
            dist_m["per_round"] == sync_m["per_round"]
            and same_globals(sync_g[0], dist_g[0])),
        "final_acc": dist_m["final_acc"],
    }
    assert degenerate["trajectory_equal"], \
        "degenerate distributed must be bit-identical to sync"

    # -- chaos (defended): pod kill + 5% corruption under quorum ---------
    chaos_m, _, _ = one(
        _config(rounds,
                dist=DistConfig(n_pods=2, heartbeat_s=0.1,
                                upload_deadline_s=1.0,
                                kill_pod=1, kill_after_round=2),
                faults=FaultConfig(transport_corrupt=0.05, quorum=0.5)),
        "distributed")
    chaos = {
        "drift": chaos_m["final_acc"] - sync_m["final_acc"],
        "final_acc": chaos_m["final_acc"],
        "wire_retries": chaos_m["wire_retries"],
        "crc_failures": chaos_m["crc_failures"],
        "deadline_misses": chaos_m["deadline_misses"],
        "min_pods_alive": chaos_m["min_pods_alive"],
        "n_pods": 2,
        "finite": chaos_m["finite"],
    }

    # -- undefended: same corruption class, CRC check off ----------------
    undef_m, _, _ = one(
        _config(rounds,
                dist=DistConfig(n_pods=2, verify_crc=False),
                faults=FaultConfig(transport_corrupt=0.3)),
        "distributed")
    undefended = {
        "final_acc": undef_m["final_acc"],
        "finite": undef_m["finite"],
        "drift": undef_m["final_acc"] - sync_m["final_acc"],
        # degraded = garbage parameters actually landed: non-finite
        # globals, or accuracy more than 1pt under the clean run
        "degraded": (not undef_m["finite"]
                     or undef_m["final_acc"]
                     < sync_m["final_acc"] - 0.01),
    }

    # -- wire: bytes-on-wire per codec (fp32 baseline = degenerate run) --
    int8_m, int8_r, _ = one(
        _config(rounds, dist=DistConfig(n_pods=2, wire_codec="int8")),
        "distributed")
    bin_m, _, _ = one(
        _config(rounds, dist=DistConfig(n_pods=2, wire_codec="binarize")),
        "distributed")
    wire = {
        "fp32_bytes_up": dist_m["bytes_up"],
        "int8_bytes_up": int8_m["bytes_up"],
        "binarize_bytes_up": bin_m["bytes_up"],
        "int8_reduction_x": dist_m["bytes_up"] / max(int8_m["bytes_up"], 1),
        "binarize_reduction_x":
            dist_m["bytes_up"] / max(bin_m["bytes_up"], 1),
        "int8_final_drift": int8_m["final_acc"] - sync_m["final_acc"],
    }

    # -- restart: fusion-pod crash + wire-log replay ---------------------
    import tempfile
    with tempfile.TemporaryDirectory(prefix="dist_bench_") as td:
        wl = os.path.join(td, "wire.log")
        snap = {}

        def hook(t, globals_, state, logs, rtt):
            if t == rounds - 2:
                snap.update(globals_=list(globals_), state=state,
                            logs=[list(g) for g in logs])

        full_m, _, full_g = one(
            _config(rounds, dist=DistConfig(n_pods=2, wire_log=wl)),
            "distributed", round_end_hook=hook)
        replayed0 = REGISTRY.counter("dist.wirelog_replayed").value()
        res_m, res_r, res_g = one(
            _config(rounds, dist=DistConfig(n_pods=2, wire_log=wl)),
            "distributed", init_globals=snap["globals_"],
            init_state=snap["state"], init_logs=snap["logs"],
            start_round=rounds - 1)
        replayed = (REGISTRY.counter("dist.wirelog_replayed").value()
                    - replayed0)
    restart = {
        "trajectory_equal": (res_m["per_round"] == full_m["per_round"]
                             and same_globals(full_g[0], res_g[0])),
        "replayed": int(replayed),
        "resumed_round_bytes_up":
            int(res_r.logs[rounds - 2].wire_bytes_up),
    }

    rec = {
        "K": K, "dim": DIM, "classes": CLASSES, "rounds": rounds,
        "clean_final_acc": sync_m["final_acc"],
        "degenerate": degenerate,
        "chaos": chaos,
        "undefended": undefended,
        "wire": wire,
        "restart": restart,
    }
    emit("dist_chaos_drift", abs(chaos["drift"]) * 1e6,
         f"undef_drift_{undefended['drift']:.3f}", record=rec)
    finish_bench("dist", rec, out=OUT, config={"K": K, "rounds": rounds})
    print(f"wrote {OUT}: clean {sync_m['final_acc']:.4f}, chaos "
          f"{chaos['final_acc']:.4f} (drift {chaos['drift']:+.4f}, "
          f"retries {chaos['wire_retries']}, pods_alive "
          f"{chaos['min_pods_alive']}/2), undefended "
          f"{undefended['final_acc']:.4f} (degraded "
          f"{undefended['degraded']}), int8 wire x"
          f"{wire['int8_reduction_x']:.2f}, restart replayed "
          f"{restart['replayed']}")


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
