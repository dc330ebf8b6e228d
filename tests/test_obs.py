"""Flight recorder / metrics registry / perf history (docs/observability.md).

 1. Disarmed is FREE and EXACT: ``span()`` hands back a shared no-op,
    and an armed-but-idle run reproduces the disarmed trajectory
    bit-identically across strategies and drivers.
 2. Armed spans are well-formed: monotonic timestamps, correct nesting
    (depth/parent), phase coverage of every RoundEngine phase, driver
    attribution, and a loadable JSONL stream.
 3. The metrics registry is one enumerable home for counters/gauges/
    histograms; the legacy ``TraceCounter`` aliases share its state;
    per-round streaming emits counter DELTAS through pluggable sinks.
 4. ``ObsSpec`` round-trips through JSON, rejects unknown keys, and old
    spec dicts (no ``obs`` section) load with defaults.
 5. Telemetry survives resume: an interrupted traced+streamed run,
    resumed, yields gap-free merged streams and the exact uninterrupted
    trajectory.
 6. The perf history is a validated, versioned contract:
    ``make/append/load/latest`` round-trip, malformed records fail
    loudly, and ``benchmarks.check_history`` gates regressions.
"""
import json
import os

import pytest

from repro.api import (CohortSpec, DriverSpec, Experiment, ExperimentSpec,
                       FusionSpec, ModelSpec, ObsSpec, PartitionSpec,
                       SourceSpec, StrategySpec, TaskSpec)
from repro.obs import history, metrics, trace
from repro.obs.metrics import (Counter, Gauge, Histogram, MemorySink,
                               MetricsObserver, MetricsRegistry, REGISTRY)


def small_fusion():
    return FusionSpec(max_steps=50, patience=50, eval_every=25,
                      batch_size=32)


def toy_spec(strategy="fedavg", rounds=2, driver=None, obs=None):
    return ExperimentSpec(
        task=TaskSpec(name="blobs", n_samples=1200),
        partition=PartitionSpec(n_clients=6, alpha=1.0),
        cohort=CohortSpec(prototypes=[ModelSpec("mlp",
                                                {"hidden": [16, 16]})]),
        strategy=StrategySpec(name=strategy, fusion=small_fusion()),
        source=(SourceSpec(name="unlabeled", params={"n": 500})
                if strategy == "feddf" else None),
        driver=driver or DriverSpec(),
        obs=obs or ObsSpec(),
        rounds=rounds, client_fraction=1.0, local_epochs=3,
        local_batch_size=32, local_lr=0.05, seed=0)


@pytest.fixture(autouse=True)
def _clean_recorder():
    trace.disarm()
    yield
    trace.disarm()


# ---------------------------------------------------------------------------
# trace: disarmed no-op, armed span stream
# ---------------------------------------------------------------------------

def test_disarmed_span_is_shared_noop():
    s1 = trace.span("anything", round=3)
    s2 = trace.span("else")
    assert s1 is s2  # one immortal null object, no allocation per call
    with s1 as sp:
        sp.annotate(k=1)  # no-op, no error
    assert trace.recorder() is None


def test_armed_spans_nest_and_load(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    trace.arm(path=path)
    trace.set_context(driver="sync")
    with trace.span("outer", round=0):
        with trace.span("inner", round=0):
            pass
    with trace.span("outer", round=1) as sp:
        sp.annotate(quarantined=2)
    trace.disarm()

    spans = trace.load_spans(path)
    assert [s["name"] for s in spans] == ["inner", "outer", "outer"]
    inner, outer0, outer1 = spans
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer0["depth"] == 0 and outer0["parent"] is None
    assert outer1["quarantined"] == 2
    for s in spans:
        assert s["t1"] >= s["t0"] >= 0.0
        assert s["dur_s"] == pytest.approx(s["t1"] - s["t0"])
        assert s["driver"] == "sync"
    # inner nests inside outer0's window
    assert outer0["t0"] <= inner["t0"] and inner["t1"] <= outer0["t1"]


def test_recorder_summary_totals_and_per_round(tmp_path):
    trace.arm(path=str(tmp_path / "s.jsonl"))
    for t in range(2):
        with trace.span("train_clients", round=t):
            pass
        with trace.span("join_fusion", round=t):
            pass
    rec = trace.recorder()
    s = rec.summary()
    assert s["n_spans"] == 4
    assert set(s["phase_totals_s"]) == {"train_clients", "join_fusion"}
    # idle gap is exactly the join seam total
    assert s["idle_gap_s"] == pytest.approx(
        s["phase_totals_s"]["join_fusion"])
    assert set(s["per_round"]) == {"0", "1"}
    assert "train_clients" in s["per_round"]["0"]


def test_rearm_closes_previous_recorder(tmp_path):
    trace.arm(path=str(tmp_path / "a.jsonl"))
    first = trace.recorder()
    trace.arm(path=str(tmp_path / "b.jsonl"))
    assert trace.recorder() is not first
    with trace.span("x"):
        pass
    trace.disarm()
    assert trace.load_spans(str(tmp_path / "a.jsonl")) == []
    assert len(trace.load_spans(str(tmp_path / "b.jsonl"))) == 1


def test_profiler_start_failure_raises_and_arms_nothing(tmp_path,
                                                         monkeypatch):
    import jax

    def refuse(log_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        trace.arm(path=str(tmp_path / "s.jsonl"),
                  profile_dir=str(tmp_path / "prof"))
    assert trace.recorder() is None


# ---------------------------------------------------------------------------
# metrics registry + sinks
# ---------------------------------------------------------------------------

def test_registry_instruments():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    assert reg.counter("a.b") is c  # get-or-create shares state
    c.add(3)
    g = reg.gauge("g")
    h = reg.histogram("h")
    assert reg.snapshot() == {"a.b": 3}  # unset gauge/hist omitted
    g.set(7.5)
    h.observe(1.0)
    h.observe(3.0)
    snap = reg.snapshot()
    assert snap["g"] == 7.5
    assert snap["h"]["count"] == 2 and snap["h"]["mean"] == 2.0
    assert snap["h"]["min"] == 1.0 and snap["h"]["max"] == 3.0
    reg.reset()
    # reset zeroes counters (still enumerable) and clears gauge/hist
    assert reg.snapshot() == {"a.b": 0}


def test_registry_type_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_trace_counter_alias_is_registry_counter():
    from repro.common.counters import TraceCounter
    assert TraceCounter is Counter
    # the migrated module singletons live in the global registry
    from repro.core.client import CLIENT_COMPILES
    assert REGISTRY.counter("core.client.compiles") is CLIENT_COMPILES


class _Event:
    def __init__(self, round, test_acc, val_acc):
        self.round, self.group = round, 0
        self.log = type("L", (), {"test_acc": test_acc,
                                  "val_acc": val_acc})()


def test_metrics_observer_emits_counter_deltas():
    reg = MetricsRegistry()
    c = reg.counter("n.compiles")
    sink = MemorySink()
    obs = MetricsObserver([sink], registry=reg)
    c.add(5)
    obs(_Event(0, 0.5, 0.4))
    c.add(2)
    obs(_Event(1, 0.6, 0.5))
    obs.close()
    r0, r1 = sink.records
    assert (r0["round"], r0["n.compiles"]) == (0, 5)
    assert (r1["round"], r1["n.compiles"]) == (1, 2)  # delta, not total
    assert r1["test_acc"] == 0.6


# ---------------------------------------------------------------------------
# ObsSpec
# ---------------------------------------------------------------------------

def test_obs_spec_round_trip():
    spec = toy_spec(obs=ObsSpec(trace=True, metrics_dir="m"))
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.obs.enabled


def test_obs_spec_unknown_key_rejected():
    d = toy_spec().to_dict()
    d["obs"]["tracing"] = True
    with pytest.raises(ValueError, match="unknown"):
        ExperimentSpec.from_dict(d)


def test_old_spec_without_obs_loads_with_defaults():
    d = toy_spec().to_dict()
    del d["obs"]
    spec = ExperimentSpec.from_dict(d)
    assert spec.obs == ObsSpec()
    assert not spec.obs.enabled


def test_profile_without_dir_fails_validation():
    with pytest.raises(ValueError, match="profile_dir"):
        toy_spec(obs=ObsSpec(profile=True)).validate()


# ---------------------------------------------------------------------------
# end-to-end: bit-identity, summary surface, resume telemetry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,driver", [
    ("fedavg", None),
    ("feddf", None),
    ("fedavg", "buffered_async"),
])
def test_armed_idle_trajectory_bit_identical(tmp_path, strategy, driver):
    drv = DriverSpec(kind=driver) if driver else None
    plain = Experiment(toy_spec(strategy=strategy, driver=drv)).run()
    armed = Experiment(toy_spec(
        strategy=strategy, driver=drv,
        obs=ObsSpec(trace=True,
                    trace_path=str(tmp_path / "spans.jsonl"),
                    metrics_dir=str(tmp_path / "m")))).run()
    assert armed.result.logs == plain.result.logs
    assert plain.obs is None and armed.obs is not None
    assert armed.summary()["obs"]["n_spans"] > 0
    assert "per_round" in armed.summary()["obs"]
    # every engine phase shows up in the armed run's breakdown
    # (buffered_async samples cohorts through the population subsystem,
    # not engine.sample_cohort, and nests waves under "fill")
    phases = set(armed.obs["phase_totals_s"])
    assert {"build_round_batches", "train_clients",
            "aggregate", "evaluate_round"} <= phases
    if driver is None:
        assert "sample_cohort" in phases
    else:
        assert {"fill", "wave"} <= phases
    spans = trace.load_spans(str(tmp_path / "spans.jsonl"))
    assert spans and all("t1" in s for s in spans)
    # metrics stream: one record per (round, group) with counter columns
    lines = [json.loads(l) for l in
             open(tmp_path / "m" / "metrics.jsonl")]
    # rounds are 1-based in RoundEvent
    assert [r["round"] for r in lines] == list(range(1, len(lines) + 1))
    assert all("core.client.compiles" in r for r in lines)
    assert os.path.exists(tmp_path / "m" / "metrics.csv")


class _StopAfter(Exception):
    pass


def test_telemetry_across_resume_gap_free(tmp_path):
    """Kill a traced+streamed run mid-flight; the resumed run appends to
    the same streams (gap-free rounds) and reproduces the uninterrupted
    disarmed trajectory exactly."""
    obs = ObsSpec(trace=True, trace_path=str(tmp_path / "spans.jsonl"),
                  metrics_dir=str(tmp_path / "m"))
    plain = Experiment(toy_spec(strategy="fedavg", rounds=4)).run()

    def bomb(event):
        if event.round == 3:
            raise _StopAfter

    ckpt_dir = str(tmp_path / "run")
    with pytest.raises(_StopAfter):
        Experiment(toy_spec(strategy="fedavg", rounds=4, obs=obs)).run(
            observers=[bomb], checkpoint_dir=ckpt_dir)
    assert trace.recorder() is None  # disarmed even on the error path

    resumed = Experiment.resume(ckpt_dir)
    assert resumed.result.logs == plain.result.logs  # bit-identical

    rounds = [json.loads(l)["round"]
              for l in open(tmp_path / "m" / "metrics.jsonl")]
    # appended, not truncated: both segments present, no round missing
    # (rounds are 1-based in RoundEvent)
    assert sorted(set(rounds)) == [1, 2, 3, 4]
    spans = trace.load_spans(str(tmp_path / "spans.jsonl"))
    seen = {s.get("round") for s in spans if "round" in s}
    assert {1, 2, 3, 4} <= seen  # both segments' engine spans present


# ---------------------------------------------------------------------------
# perf history contract
# ---------------------------------------------------------------------------

def test_history_round_trip(tmp_path):
    path = str(tmp_path / "h.jsonl")
    rec = history.make_record("driver", {"speedup": 1.4}, case="toy",
                              config={"K": 8})
    history.append(rec, path=path)
    history.append(history.make_record("driver", {"speedup": 1.6},
                                       case="toy"), path=path)
    back = history.load(path)
    assert len(back) == 2 and back[0] == rec
    assert back[0]["schema_version"] == history.SCHEMA_VERSION
    assert back[0]["machine"]["python"]
    assert back[0]["config"] == {"K": 8}
    latest = history.latest(path)
    assert latest[("driver", "toy")]["metrics"]["speedup"] == 1.6


def test_history_validation_fails_loudly(tmp_path):
    rec = history.make_record("b", {})
    bad = dict(rec)
    bad["extra_key"] = 1
    with pytest.raises(ValueError, match="unknown"):
        history.validate_record(bad)
    missing = {k: v for k, v in rec.items() if k != "machine"}
    with pytest.raises(ValueError, match="missing"):
        history.validate_record(missing)
    wrong = dict(rec, schema_version=99)
    with pytest.raises(ValueError, match="schema_version"):
        history.validate_record(wrong)
    path = str(tmp_path / "h.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
        f.write("{\"not\": \"a record\"}\n")
    with pytest.raises(ValueError, match=":2"):
        history.load(path)


def test_history_load_absent_is_empty(tmp_path):
    assert history.load(str(tmp_path / "nope.jsonl")) == []
    assert history.latest(str(tmp_path / "nope.jsonl")) == {}


def _with_cpus(rec, cpus):
    rec = dict(rec)
    rec["machine"] = dict(rec["machine"], cpus=cpus)
    return rec


def test_check_history_gates(tmp_path):
    from benchmarks import check_history
    path = str(tmp_path / "h.jsonl")
    good = {"speedup": 1.4, "async_staleness0": {"trajectory_equal": True}}
    history.append(_with_cpus(history.make_record("driver", good), 4),
                   path=path)
    assert check_history.check(path) == []
    assert check_history.main(["--history", path,
                               "--require", "driver"]) == 0
    # a required-but-absent bench fails
    assert check_history.main(["--history", path,
                               "--require", "bucketing"]) == 1
    # a regressed latest record fails with the same threshold text
    bad = {"speedup": 1.05, "async_staleness0": {"trajectory_equal": True}}
    history.append(_with_cpus(history.make_record("driver", bad), 4),
                   path=path)
    failures = check_history.check(path)
    assert failures and "overlap speedup regressed" in failures[0]
    assert check_history.main(["--history", path]) == 1


def test_check_history_one_core_skips_overlap_gates(tmp_path, capsys):
    """A 1-core machine fingerprint can't demonstrate thread overlap:
    those sub-gates SKIP (visibly) instead of failing — or passing."""
    from benchmarks import check_history
    path = str(tmp_path / "h.jsonl")
    # speedup 1.0 would FAIL on a multi-core record; on one core it skips
    m = {"speedup": 1.0, "async_staleness0": {"trajectory_equal": True}}
    history.append(_with_cpus(history.make_record("driver", m), 1),
                   path=path)
    pop = {"buffered_degenerate": {"trajectory_equal": True},
           "uploads_ratio": 1.0, "final_acc_drift": 0.0}
    history.append(_with_cpus(history.make_record("population", pop), 1),
                   path=path)
    assert check_history.check(path) == []
    out = capsys.readouterr().out
    assert "SKIP driver" in out and "1-core machine" in out
    assert "SKIP population" in out
    # the correctness sub-gates of the same record still fail
    m_bad = {"speedup": 1.0,
             "async_staleness0": {"trajectory_equal": False}}
    history.append(_with_cpus(history.make_record("driver", m_bad), 1),
                   path=path)
    failures = check_history.check(path)
    assert failures and "trajectory drifted" in failures[0]
