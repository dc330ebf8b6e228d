"""Round-driver subsystem (docs/drivers.md).

 1. The ``sync`` driver IS the historic loop: trajectories through
    ``run_rounds``/``Experiment.run`` are bit-identical to the legacy
    entry points, and ``async_pipelined`` with ``staleness=0`` matches
    them exactly too (pinning sync == async(0) == legacy).
 2. ``async_pipelined`` with ``staleness=1`` overlaps round t's fusion
    with round t+1's training; killed mid-pipeline and resumed, the
    trajectory equals an uninterrupted async run (the checkpoint carries
    the stale training base).
 3. ``DriverSpec`` round-trips as JSON and validates kind / staleness /
    prefetch against the driver registry.
 4. Early stopping: ``target_accuracy`` now stops HETEROGENEOUS runs
    too, and any observer can stop a run via
    ``RoundEvent.request_stop``.
 5. The jitted FedDF chunk is cached ACROSS rounds — the compile counter
    shows one trace for a whole multi-round run.
 6. The ``multihost`` driver reproduces sync trajectories on a 4-way
    simulated host mesh, and ``drive_fed_rounds`` actually drives the
    production ``make_fed_round_step`` loop (subprocesses with forced
    host devices).
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import (CohortSpec, DriverSpec, Experiment, ExperimentSpec,
                       FusionSpec, ModelSpec, PartitionSpec, SourceSpec,
                       StrategySpec, TaskSpec)
from repro.core import (FLConfig, FusionConfig, mlp, run_federated,
                        run_rounds)
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)
from repro.drivers import (AsyncPipelinedDriver, Driver, MultiHostDriver,
                           SyncDriver, available_drivers, get_driver,
                           make_driver, resolve_driver, unwrap_state,
                           wrap_state)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def problem():
    ds = gaussian_mixture(1200, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    parts = dirichlet_partition(train.y, 6, 1.0, seed=0)
    src = UnlabeledDataset(np.random.default_rng(1).uniform(
        -3, 3, (500, 2)).astype(np.float32))
    return train, val, test, parts, src


def small_cfg(strategy="feddf", rounds=2, **kw):
    return FLConfig(strategy=strategy, rounds=rounds, client_fraction=0.5,
                    local_epochs=3, local_batch_size=32, local_lr=0.05,
                    seed=0, fusion=FusionConfig(max_steps=50, patience=50,
                                                eval_every=25,
                                                batch_size=32), **kw)


def _assert_same_run(a, b):
    """(results, globals, rtt) triples must match bit-for-bit."""
    res_a, glob_a, rtt_a = a
    res_b, glob_b, rtt_b = b
    assert rtt_a == rtt_b
    assert len(res_a) == len(res_b)
    for ra, rb in zip(res_a, res_b):
        assert ra.logs == rb.logs
    for ga, gb in zip(glob_a, glob_b):
        for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_builtins():
    assert {"sync", "async_pipelined", "multihost"} <= \
        set(available_drivers())
    assert get_driver("sync") is SyncDriver
    assert isinstance(make_driver("async_pipelined", staleness=1),
                      AsyncPipelinedDriver)
    with pytest.raises(ValueError, match="unknown driver"):
        get_driver("no-such-driver")


def test_resolve_driver():
    assert isinstance(resolve_driver(None), SyncDriver)
    assert isinstance(resolve_driver("multihost"), MultiHostDriver)
    drv = AsyncPipelinedDriver(staleness=1)
    assert resolve_driver(drv) is drv
    with pytest.raises(TypeError, match="driver must be"):
        resolve_driver(42)


def test_driver_knob_validation():
    with pytest.raises(ValueError, match="staleness"):
        AsyncPipelinedDriver(staleness=-1)
    # bounded staleness is a ring now: any S >= 0 constructs
    assert AsyncPipelinedDriver(staleness=3).staleness == 3
    with pytest.raises(ValueError, match="prefetch"):
        SyncDriver(prefetch=-1)
    # sync-semantics drivers refuse a staleness they would silently
    # ignore (mirrors DriverSpec validation)
    with pytest.raises(ValueError, match="async_pipelined"):
        SyncDriver(staleness=1)
    with pytest.raises(ValueError, match="async_pipelined"):
        MultiHostDriver(staleness=1)


def test_wrap_unwrap_state_round_trip():
    st, prev = unwrap_state(wrap_state([1, 2], {"w": 3}))
    assert st == [1, 2] and prev == {"w": 3}
    assert unwrap_state("plain") == ("plain", None)
    assert unwrap_state({"strategy_state": 1}) == ({"strategy_state": 1},
                                                   None)


# ---------------------------------------------------------------------------
# trajectory pinning: sync == async(staleness=0) == legacy
# ---------------------------------------------------------------------------

def test_sync_and_async0_match_legacy(problem):
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16, 16))
    cfg = small_cfg()

    legacy = run_federated(net, train, parts, val, test, cfg, source=src)

    def run(driver):
        return run_rounds([net], [0] * len(parts), train, parts, val, test,
                          cfg, source=src, driver=driver)

    sync = run("sync")
    async0 = run(make_driver("async_pipelined", staleness=0, prefetch=2))
    _assert_same_run(sync, async0)
    assert sync[0][0].logs == legacy.logs
    for x, y in zip(jax.tree.leaves(sync[1][0]),
                    jax.tree.leaves(legacy.global_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_async0_matches_sync_heterogeneous(problem):
    train, val, test, parts, src = problem
    nets = [mlp(2, 3, hidden=(12,), name="p-s"),
            mlp(2, 3, hidden=(24,), name="p-m")]
    proto = [k % 2 for k in range(len(parts))]
    cfg = small_cfg()

    def run(driver):
        return run_rounds(nets, proto, train, parts, val, test, cfg,
                          source=src, heterogeneous=True, driver=driver)

    _assert_same_run(run("sync"),
                     run(make_driver("async_pipelined", staleness=0)))


def test_async_staleness1_completes_all_rounds(problem):
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16, 16))
    cfg = small_cfg(rounds=3)
    results, globals_, rtt = run_rounds(
        [net], [0] * len(parts), train, parts, val, test, cfg, source=src,
        driver=make_driver("async_pipelined", staleness=1, prefetch=2))
    assert [l.round for l in results[0].logs] == [1, 2, 3]
    assert rtt is None
    assert results[0].final_acc > 1.0 / 3  # above chance despite staleness


# ---------------------------------------------------------------------------
# DriverSpec: serialization + validation + Experiment wiring
# ---------------------------------------------------------------------------

def api_spec(driver=None, strategy="fedavgm", rounds=2, **kw):
    return ExperimentSpec(
        task=TaskSpec(name="blobs", n_samples=1200),
        partition=PartitionSpec(n_clients=6, alpha=1.0),
        cohort=CohortSpec(prototypes=[ModelSpec("mlp",
                                                {"hidden": [16, 16]})]),
        strategy=StrategySpec(name=strategy,
                              fusion=FusionSpec(max_steps=50, patience=50,
                                                eval_every=25,
                                                batch_size=32)),
        source=(SourceSpec(name="unlabeled", params={"n": 500})
                if strategy == "feddf" else None),
        driver=driver if driver is not None else DriverSpec(),
        rounds=rounds, client_fraction=0.5, local_epochs=3,
        local_batch_size=32, local_lr=0.05, seed=0, **kw)


def test_driver_spec_round_trips():
    spec = api_spec(DriverSpec(kind="async_pipelined", staleness=1,
                               prefetch=3))
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.to_dict()["driver"] == {"kind": "async_pipelined",
                                        "staleness": 1, "prefetch": 3}
    # specs predating the driver axis still load (default: sync)
    d = spec.to_dict()
    del d["driver"]
    assert ExperimentSpec.from_dict(d).driver == DriverSpec()


@pytest.mark.parametrize("driver,match", [
    (DriverSpec(kind="no-such-driver"), "unknown driver"),
    (DriverSpec(kind="async_pipelined", staleness=-1), "staleness"),
    (DriverSpec(kind="buffered_async", staleness=2), "buffered_async"),
    (DriverSpec(kind="sync", staleness=1), "only applies"),
    (DriverSpec(kind="async_pipelined", prefetch=-1), "prefetch"),
])
def test_driver_spec_validation(driver, match):
    with pytest.raises(ValueError, match=match):
        api_spec(driver).validate()


def test_experiment_async0_matches_sync_exactly():
    sync = Experiment(api_spec(strategy="feddf")).run()
    async0 = Experiment(api_spec(
        DriverSpec(kind="async_pipelined", staleness=0, prefetch=2),
        strategy="feddf")).run()
    assert async0.result.logs == sync.result.logs
    for a, b in zip(jax.tree.leaves(async0.global_params[0]),
                    jax.tree.leaves(sync.global_params[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# async resume: kill mid-pipeline, resume, trajectory equality
# ---------------------------------------------------------------------------

class _StopAfter(Exception):
    pass


@pytest.mark.parametrize("strategy,staleness", [("fedavgm", 1),
                                                ("feddf", 1),
                                                ("feddf", 0)])
def test_async_resume_matches_uninterrupted(tmp_path, strategy, staleness):
    """Kill an async-pipelined checkpointed run mid-pipeline (round t+1's
    training already dispatched when round t's hook fires); the resumed
    run must reproduce the uninterrupted async trajectory exactly — the
    staleness=1 checkpoint carries the stale base the in-flight round
    trained from."""
    spec = api_spec(DriverSpec(kind="async_pipelined", staleness=staleness,
                               prefetch=2),
                    strategy=strategy, rounds=5)
    baseline = Experiment(spec).run()
    assert [l.round for l in baseline.result.logs] == [1, 2, 3, 4, 5]

    def bomb(event):
        if event.round == 3:
            raise _StopAfter

    ckpt_dir = str(tmp_path / f"run-{strategy}-{staleness}")
    with pytest.raises(_StopAfter):
        Experiment(spec).run(observers=[bomb], checkpoint_dir=ckpt_dir)
    assert os.path.isdir(os.path.join(ckpt_dir, "rounds", "00002"))

    resumed = Experiment.resume(ckpt_dir)
    assert resumed.result.logs == baseline.result.logs
    for a, b in zip(jax.tree.leaves(resumed.global_params[0]),
                    jax.tree.leaves(baseline.global_params[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# early stopping: heterogeneous target_accuracy + observer request_stop
# ---------------------------------------------------------------------------

def test_heterogeneous_target_accuracy_stops_early():
    spec = dataclasses.replace(
        api_spec(strategy="fedavg", rounds=6),
        cohort=CohortSpec(prototypes=[
            ModelSpec("mlp", {"hidden": [12], "name": "p-s"}),
            ModelSpec("mlp", {"hidden": [24], "name": "p-m"})]),
        target_accuracy=0.34)  # just above chance: reached immediately
    res = Experiment(spec).run()
    assert res.heterogeneous
    assert res.rounds_to_target is not None
    assert res.rounds_to_target < 6
    for r in res.results:  # the run really stopped, all groups truncated
        assert len(r.logs) == res.rounds_to_target
    assert max(l.test_acc for l in
               [r.logs[-1] for r in res.results]) >= 0.34


def test_observer_request_stop_ends_run():
    events = []

    def stopper(event):
        events.append(event.round)
        if event.round == 2:
            event.request_stop()

    res = Experiment(api_spec(strategy="fedavg", rounds=5)).run(
        observers=[stopper])
    assert [l.round for l in res.result.logs] == [1, 2]
    # observer stops are soft: no rounds-to-target claim
    assert res.rounds_to_target is None


def test_observer_request_stop_under_async(problem):
    spec = api_spec(DriverSpec(kind="async_pipelined", staleness=1),
                    strategy="fedavg", rounds=5)

    def stopper(event):
        if event.round == 2:
            event.request_stop()

    res = Experiment(spec).run(observers=[stopper])
    assert [l.round for l in res.result.logs] == [1, 2]


# ---------------------------------------------------------------------------
# cross-round compiled-chunk reuse (the recompile-per-round fix)
# ---------------------------------------------------------------------------

def test_feddf_chunk_compiles_once_across_rounds(problem):
    from repro.core.feddf import CHUNK_COMPILES
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16, 16))
    CHUNK_COMPILES.reset()
    run_federated(net, train, parts, val, test, small_cfg(rounds=3),
                  source=src)
    # one trace for the whole run: rounds 2..3 reuse round 1's program
    assert CHUNK_COMPILES.count == 1, CHUNK_COMPILES.count


def test_feddf_bank_forward_compiles_once_across_rounds(problem):
    from repro.core.logit_bank import BANK_COMPILES
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16, 16))
    BANK_COMPILES.reset()
    res = run_federated(net, train, parts, val, test, small_cfg(rounds=3),
                        source=src)
    # every round builds a bank from fresh uploads, one trace in all
    assert [l.bank for l in res.logs] == ["bank"] * 3
    assert BANK_COMPILES.count == 1, BANK_COMPILES.count


def test_feddf_chunk_cache_shared_across_drivers(problem):
    """The async driver's fusion thread must reuse the same compiled
    chunk the sync path built (same net/source/fusion config)."""
    from repro.core.feddf import CHUNK_COMPILES
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16, 16))
    cfg = small_cfg(rounds=2)
    run_rounds([net], [0] * len(parts), train, parts, val, test, cfg,
               source=src, driver="sync")
    CHUNK_COMPILES.reset()
    run_rounds([net], [0] * len(parts), train, parts, val, test, cfg,
               source=src,
               driver=make_driver("async_pipelined", staleness=1))
    assert CHUNK_COMPILES.count == 0, CHUNK_COMPILES.count


# ---------------------------------------------------------------------------
# multihost driver (forced host devices in subprocesses)
# ---------------------------------------------------------------------------

def test_multihost_driver_matches_sync_on_4_device_mesh():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {src!r})
import numpy as np
import jax
from repro.core import FLConfig, mlp, run_rounds
from repro.data import (dirichlet_partition, gaussian_mixture,
                        train_val_test_split)

assert len(jax.devices()) == 4
ds = gaussian_mixture(1200, n_classes=3, dim=2, seed=0)
train, val, test = train_val_test_split(ds)
parts = dirichlet_partition(train.y, 8, 1.0, seed=0)
cfg = FLConfig(strategy="fedavg", rounds=2, client_fraction=0.5,
               local_epochs=2, local_batch_size=32, local_lr=0.05, seed=0)
net = mlp(2, 3, hidden=(16,))
sync, _, _ = run_rounds([net], [0] * 8, train, parts, val, test, cfg,
                        driver="sync")
mh, _, _ = run_rounds([net], [0] * 8, train, parts, val, test, cfg,
                      driver="multihost")
assert [l.test_acc for l in mh[0].logs] == \\
    [l.test_acc for l in sync[0].logs], (mh[0].logs, sync[0].logs)
# indivisible cohorts fail loudly, not deep inside shard_map
cfg_bad = FLConfig(strategy="fedavg", rounds=1, client_fraction=0.375,
                   local_epochs=1, seed=0)  # 3 active on 4 devices
try:
    run_rounds([net], [0] * 8, train, parts, val, test, cfg_bad,
               driver="multihost")
except ValueError as e:
    assert "do not divide" in str(e), e
else:
    raise AssertionError("expected divisibility ValueError")
print("MULTIHOST_DRIVER_OK")
""".format(src=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.stdout.count("MULTIHOST_DRIVER_OK") == 1, r.stdout + r.stderr


def test_drive_fed_rounds_production_loop():
    """make_fed_round_step finally has a driver: compile once, push the
    global to the stacked client axis, local-SGD on the mesh, FedAvg the
    uploads — two real rounds on a 4-device simulated host mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, sys
sys.path.insert(0, {src!r})
import numpy as np
from repro.configs.qwen3_8b import CONFIG
from repro.drivers import drive_fed_rounds
from repro.launch.mesh import make_host_mesh
cfg = dataclasses.replace(CONFIG, n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=4, d_ff=128, vocab_size=256,
                          head_dim=16)
mesh = make_host_mesh(2, 2)
params, stats = drive_fed_rounds(cfg, mesh, rounds=2, n_clients=4,
                                 local_steps=2, batch_size=2, seq_len=16)
assert [s["round"] for s in stats] == [1, 2], stats
assert all(np.isfinite(s["update_norm"]) and s["update_norm"] > 0
           for s in stats), stats
print("FED_ROUND_DRIVER_OK")
""".format(src=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.stdout.count("FED_ROUND_DRIVER_OK") == 1, r.stdout + r.stderr
