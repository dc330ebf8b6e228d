"""Round-engine microbenchmarks.

Case ``engine`` (ISSUE 1 acceptance): per-round client training
wall-clock, sequential python-loop (`make_local_update` per client) vs
the vectorized engine path (`make_batched_local_update`, one jitted
vmap-over-clients scan).  Equal-size partitions, so neither path pays
padding; both are warmed up before timing so the numbers compare
steady-state rounds, not compiles.  Emits
``round_engine_K{K},us_per_round,speedup`` per client count.

Case ``bucketing`` (ISSUE 5 acceptance): the heterogeneous skewed-cohort
client phase — Dirichlet alpha=0.1, K=16 clients over G=2 prototypes —
with and without step-count bucketing (docs/bucketing.md).  On this
split the largest client has tens of times the local steps of the
median, so the unbucketed path pads most vmapped lanes with masked
no-op steps; bucketing removes them without touching the trajectory
(the bench asserts bit-identical round logs and globals).  Records the
padded-step waste of both paths and the MARGINAL real-client-steps/sec
(steady-state rounds after a warm-up that absorbs every bucket's
compile; ``benchmarks/timing.py``) into ``BENCH_bucketing.json``
(override with ``BENCH_BUCKETING_OUT``) for CI's bench-smoke gate.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, scale
from benchmarks.timing import finish_bench, time_rounds
from repro.core import BucketConfig, FLConfig, mlp, run_rounds
from repro.core.client import (build_batched_batches, build_batches,
                               make_batched_local_update, make_local_update)
from repro.core.engine import RoundEngine
from repro.data import (dirichlet_partition, gaussian_mixture,
                        train_val_test_split)
from repro.optim.optimizers import sgd

SAMPLES_PER_CLIENT = 256
BATCH = 32
EPOCHS = 8
LR = 0.05
OUT = os.environ.get("BENCH_BUCKETING_OUT", "BENCH_bucketing.json")

# skewed heterogeneous case (ISSUE 5 acceptance config)
SKEW_K = 16
SKEW_ALPHA = 0.1
SKEW_DIM, SKEW_CLASSES = 16, 5
SKEW_EPOCHS = 6
SKEW_HIDDEN = ((96,), (192,))


def _problem(k: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    n = k * SAMPLES_PER_CLIENT
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = rng.integers(0, 3, size=n)
    parts = [np.arange(i * SAMPLES_PER_CLIENT, (i + 1) * SAMPLES_PER_CLIENT)
             for i in range(k)]
    return x, y, parts


def run_engine_case() -> None:
    rounds = scale(3, 10)
    net = mlp(2, 3, hidden=(32, 32))
    g = net.init(jax.random.PRNGKey(0))

    for k in (4, 8, 16):
        x, y, parts = _problem(k)

        upd = make_local_update(net, sgd(LR))
        per = [build_batches(x[idx], y[idx], BATCH, EPOCHS, seed=i)
               for i, idx in enumerate(parts)]
        per = [(jnp.asarray(xb), jnp.asarray(yb)) for xb, yb in per]

        def seq_round():
            outs = [upd(g, xb, yb, g) for xb, yb in per]
            jax.block_until_ready(outs[-1])

        bupd = make_batched_local_update(net, sgd(LR))
        xb, yb, mask = build_batched_batches(x, y, parts, BATCH, EPOCHS,
                                             seeds=list(range(k)))
        xb, yb, mask = jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mask)
        keys = jnp.zeros((k, 2), jnp.uint32)

        def bat_round():
            jax.block_until_ready(bupd(g, xb, yb, g, mask, keys))

        t_seq = time_rounds(seq_round, rounds)
        t_bat = time_rounds(bat_round, rounds)
        speedup = t_seq / t_bat
        emit(f"round_engine_K{k}", t_bat,
             f"speedup_x{speedup:.2f}",
             record={"n_clients": k, "seq_s": t_seq, "batched_s": t_bat,
                     "speedup": speedup, "steps_per_client":
                     EPOCHS * (SAMPLES_PER_CLIENT // BATCH)})


# ---------------------------------------------------------------------------
# skewed-cohort bucketing case
# ---------------------------------------------------------------------------

def _skew_problem(seed: int = 0):
    ds = gaussian_mixture(scale(8000, 12_000), n_classes=SKEW_CLASSES,
                          dim=SKEW_DIM, seed=seed)
    train, val, test = train_val_test_split(ds, seed=seed)
    parts = dirichlet_partition(train.y, SKEW_K, SKEW_ALPHA, seed=seed)
    nets = [mlp(SKEW_DIM, SKEW_CLASSES, hidden=SKEW_HIDDEN[0],
                name="proto-s"),
            mlp(SKEW_DIM, SKEW_CLASSES, hidden=SKEW_HIDDEN[1],
                name="proto-m")]
    proto = [k % 2 for k in range(SKEW_K)]
    return train, val, test, parts, nets, proto


def _skew_cfg(rounds: int, bucketing: BucketConfig) -> FLConfig:
    return FLConfig(strategy="fedavg", rounds=rounds, client_fraction=1.0,
                    local_epochs=SKEW_EPOCHS, local_batch_size=BATCH,
                    local_lr=LR, seed=0, bucketing=bucketing)


def _client_phase_stats(bucketing: BucketConfig, rounds: int):
    """Steady-state wall-clock of the CLIENT phase (batch build + batched
    training, the part bucketing changes) per round, plus the
    padding-waste accounting the engine's RoundBatches carry.

    ``client_fraction=1.0`` makes every round activate every client, so
    all (prototype, bucket) shapes compile during the warm-up round that
    :func:`benchmarks.timing.time_rounds` discards — the timed rounds are
    marginal steady state, the same quantity driver_bench's short-vs-long
    difference isolates."""
    train, val, test, parts, nets, proto = _skew_problem()
    engine = RoundEngine(nets, proto, train, parts, val, test,
                         _skew_cfg(rounds, bucketing), heterogeneous=True)
    globals_ = engine.init_globals()
    rng = engine.make_rng()
    active = engine.sample_cohort(rng)
    acct = engine.build_round_batches(1, active)
    real = sum(rb.real_steps for rb in acct if rb is not None)
    padded = sum(rb.padded_slots for rb in acct if rb is not None)

    t_holder = [0]

    def round_fn():
        t_holder[0] += 1
        batches = engine.build_round_batches(t_holder[0], active)
        groups = engine.train_clients(t_holder[0], globals_, batches)
        jax.block_until_ready(
            [jax.tree.leaves(g.stack)[0] for g in groups
             if g.stack is not None])

    t_round = time_rounds(round_fn, rounds)
    return {
        "kind": bucketing.kind, "max_buckets": bucketing.max_buckets,
        "round_s": t_round,
        "rounds_per_s": 1.0 / max(t_round, 1e-9),
        "real_steps_per_round": real,
        "padded_slots_per_round": padded,
        "wasted_steps_per_round": padded - real,
        "steps_per_s": real / max(t_round, 1e-9),
    }


def _trajectories_equal() -> bool:
    """Bucketed and unbucketed full runs must be bit-identical."""
    train, val, test, parts, nets, proto = _skew_problem()

    def full_run(bucketing):
        return run_rounds(nets, proto, train, parts, val, test,
                          _skew_cfg(2, bucketing), heterogeneous=True)

    base = full_run(BucketConfig())
    buck = full_run(BucketConfig(kind="pow2", max_buckets=4))
    if any(ra.logs != rb.logs for ra, rb in zip(base[0], buck[0])):
        return False
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for ga, gb in zip(base[1], buck[1])
        for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)))


def run_bucketing_case() -> None:
    rounds = scale(4, 8)
    unbucketed = _client_phase_stats(BucketConfig(), rounds)
    bucketed = _client_phase_stats(
        BucketConfig(kind="pow2", max_buckets=4), rounds)

    waste_reduction = (unbucketed["wasted_steps_per_round"]
                       / max(bucketed["wasted_steps_per_round"], 1e-9))
    speedup = bucketed["steps_per_s"] / unbucketed["steps_per_s"]
    trajectory_equal = _trajectories_equal()

    rec = {
        "K": SKEW_K, "alpha": SKEW_ALPHA, "prototypes": 2,
        "dim": SKEW_DIM, "classes": SKEW_CLASSES,
        "local_epochs": SKEW_EPOCHS, "batch": BATCH,
        "rounds_long": rounds,
        "unbucketed": unbucketed, "bucketed": bucketed,
        "waste_reduction_x": waste_reduction,
        "marginal_steps_per_s_speedup": speedup,
        "trajectory_equal": trajectory_equal,
    }
    emit("round_engine_bucketing", 1.0 / max(bucketed["steps_per_s"], 1e-9),
         f"speedup_x{speedup:.2f}_waste_x{waste_reduction:.1f}", record=rec)
    finish_bench("bucketing", rec, out=OUT,
                 config={"K": SKEW_K, "alpha": SKEW_ALPHA,
                         "rounds_long": rounds})
    print(f"wrote {OUT}: bucketed steps/s x{speedup:.2f} over padded "
          f"({unbucketed['steps_per_s']:.0f} -> "
          f"{bucketed['steps_per_s']:.0f} marginal), padded-step waste "
          f"/{waste_reduction:.1f} ({unbucketed['wasted_steps_per_round']:.0f}"
          f" -> {bucketed['wasted_steps_per_round']:.0f} slots/round), "
          f"trajectory_equal={trajectory_equal}")


def run(case: str = "all") -> None:
    if case in ("all", "engine"):
        run_engine_case()
    if case in ("all", "bucketing"):
        run_bucketing_case()


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default="all",
                    choices=["all", "engine", "bucketing"])
    run(ap.parse_args().case)
