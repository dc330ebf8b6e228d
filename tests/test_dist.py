"""Distributed runtime: wire protocol + fusion/client pods
(docs/distributed.md).

 1. Wire format: frames round-trip through every payload codec; the CRC
    rejects in-flight corruption, the version field rejects foreign
    frames (checked BEFORE the CRC), truncation never crashes the
    decoder, and every codec's ``nbytes`` is an exact bytes-on-wire
    accounting (``len(encode(leaves)) == nbytes(templates)``, with
    binarize matching the ``core.quantize`` comm-bytes formula).
 2. Crash-safe record log: torn tails are dropped, never propagated;
    the wire log replays exactly one round's UPLOAD frames.
 3. Transport faults are counter-keyed draws — deterministic in
    ``(wave, pod, attempt)``, a retry is a fresh draw — and the
    transport domain deliberately does NOT arm the statistical
    defenses (``FaultConfig.enabled``).
 4. The degenerate distributed config (loopback, fp32, zero faults) is
    BIT-IDENTICAL to the ``sync`` driver — homogeneous and
    heterogeneous, any pod count.
 5. The robustness ladder: CRC failures retry without changing the
    trajectory, a killed pod re-routes through deadline + heartbeat
    liveness, quorum shortfall freezes the globals, and a restarted
    fusion pod replays in-flight uploads from the wire log.
 6. Spec/CLI surface: ``DistSpec`` validates and round-trips;
    ``launch/train.py`` flags compile to the same spec JSON that
    ``--config`` reloads; the tcp transport runs real subprocess pods.
"""
import dataclasses
import os
import struct

import jax
import numpy as np
import pytest

from repro.core import FLConfig, FusionConfig, mlp, run_rounds
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)
from repro.dist import frames as fr
from repro.dist.config import DistConfig
from repro.dist.pods import shard_clients
from repro.population.config import FaultConfig
from repro.population.faults import FaultModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def problem():
    ds = gaussian_mixture(1200, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    parts = dirichlet_partition(train.y, 6, 1.0, seed=0)
    src = UnlabeledDataset(np.random.default_rng(1).uniform(
        -3, 3, (500, 2)).astype(np.float32))
    return train, val, test, parts, src


def small_cfg(strategy="fedavg", rounds=2, **kw):
    return FLConfig(strategy=strategy, rounds=rounds, client_fraction=0.5,
                    local_epochs=3, local_batch_size=32, local_lr=0.05,
                    seed=0, fusion=FusionConfig(max_steps=50, patience=50,
                                                eval_every=25,
                                                batch_size=32), **kw)


def _assert_same_run(a, b):
    res_a, glob_a, rtt_a = a
    res_b, glob_b, rtt_b = b
    assert rtt_a == rtt_b
    for ra, rb in zip(res_a, res_b):
        assert [l.test_acc for l in ra.logs] == \
            [l.test_acc for l in rb.logs]
    for ga, gb in zip(glob_a, glob_b):
        for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _leaves():
    rng = np.random.default_rng(0)
    return [rng.normal(size=(8, 16)).astype(np.float32),
            rng.normal(size=(16,)).astype(np.float32),
            np.arange(5, dtype=np.int64)]


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec_name", ["fp32", "binarize", "int8"])
def test_frame_round_trip_all_codecs(codec_name):
    codec = fr.get_codec(codec_name)
    leaves = _leaves()
    frame = fr.Frame(kind=fr.UPLOAD, round=3, wave=7,
                     client_ids=[2, 5, 11],
                     codec_id=codec.codec_id,
                     meta={"pod": 1, "attempt": 0},
                     payload=codec.encode(leaves))
    out = fr.decode_frame(fr.encode_frame(frame))
    assert out.kind == fr.UPLOAD and out.round == 3 and out.wave == 7
    assert list(out.client_ids) == [2, 5, 11]
    assert out.meta == {"pod": 1, "attempt": 0}
    dec = fr.codec_by_id(out.codec_id).decode(out.payload, leaves)
    assert len(dec) == len(leaves)
    for d, l in zip(dec, leaves):
        assert d.shape == l.shape and d.dtype == l.dtype


def test_fp32_codec_exact():
    codec = fr.get_codec("fp32")
    leaves = _leaves()
    for d, l in zip(codec.decode(codec.encode(leaves), leaves), leaves):
        np.testing.assert_array_equal(d, l)


def test_int8_codec_close():
    codec = fr.get_codec("int8")
    leaves = _leaves()[:2]
    dec = codec.decode(codec.encode(leaves), leaves)
    for d, l in zip(dec, leaves):
        tol = np.abs(l).max() / 127 + 1e-7
        assert np.abs(d - l).max() <= tol


def test_binarize_codec_sign_scale():
    codec = fr.get_codec("binarize")
    w = np.random.default_rng(3).normal(size=(16, 32)).astype(np.float32)
    (d,) = codec.decode(codec.encode([w]), [w])
    scale = np.float32(np.mean(np.abs(w)))
    np.testing.assert_array_equal(np.abs(d), np.full_like(w, scale))
    np.testing.assert_array_equal(np.sign(d), np.where(w >= 0, 1.0, -1.0))


def test_codec_nbytes_is_exact_accounting():
    from repro.core.quantize import comm_bytes
    leaves = _leaves()
    for name in fr.available_codecs():
        codec = fr.get_codec(name)
        assert len(codec.encode(leaves)) == codec.nbytes(leaves), name
    # binarize on the wire = the quantizer registry's comm-bytes
    # formula: one fp32 scale + one packed sign bit per element for
    # binarizable leaves, raw fp32 for the rest
    w = leaves[0]
    assert fr.get_codec("binarize").nbytes([w]) == (w.size + 7) // 8 + 4
    assert comm_bytes({"w": w}, binarized=True) == (w.size + 7) // 8 + 4


def test_crc_corruption_detected():
    data = bytearray(fr.encode_frame(fr.Frame(
        kind=fr.UPLOAD, round=1, client_ids=[1], payload=b"x" * 64)))
    data[-10] ^= 0xFF  # flip a payload byte
    with pytest.raises(fr.CRCError):
        fr.decode_frame(bytes(data))
    # the undefended path accepts the same bytes
    frame = fr.decode_frame(bytes(data), verify_crc=False)
    assert frame.kind == fr.UPLOAD


def test_version_mismatch_rejected_before_crc():
    data = bytearray(fr.encode_frame(fr.Frame(kind=fr.HEARTBEAT)))
    off = len(fr.MAGIC)
    struct.pack_into("<H", data, off, fr.WIRE_VERSION + 1)
    # the version check fires first: a foreign frame is a protocol
    # error, not a checksum coincidence
    with pytest.raises(fr.VersionError):
        fr.decode_frame(bytes(data))
    with pytest.raises(fr.VersionError):
        fr.decode_frame(bytes(data), verify_crc=False)


def test_truncation_and_garbage_rejected():
    data = fr.encode_frame(fr.Frame(
        kind=fr.UPLOAD, round=1, client_ids=[1, 2], payload=b"y" * 32))
    for n in (0, 3, len(fr.MAGIC) + 1, len(data) - 5):
        with pytest.raises(fr.FrameError):
            fr.decode_frame(data[:n])
    with pytest.raises(fr.FrameError):
        fr.decode_frame(b"XX" + data[2:])  # wrong magic


def test_pack_unpack_blobs():
    blobs = [b"aa", b"", b"c" * 100]
    packed = fr.pack_blobs(blobs)
    assert fr.unpack_blobs(packed, 3) == blobs
    with pytest.raises(fr.FrameError):
        fr.unpack_blobs(packed, 2)        # trailing bytes
    with pytest.raises(fr.FrameError):
        fr.unpack_blobs(packed[:-1], 3)   # truncated


def test_codec_registry():
    assert fr.available_codecs() == sorted(fr.available_codecs())
    assert {"fp32", "binarize", "int8"} <= set(fr.available_codecs())
    assert fr.codec_by_id(fr.get_codec("int8").codec_id).name == "int8"
    with pytest.raises(KeyError, match="unknown wire codec"):
        fr.get_codec("no-such-codec")
    with pytest.raises(fr.FrameError, match="unknown wire codec id"):
        fr.codec_by_id(200)


# ---------------------------------------------------------------------------
# record log + wire log
# ---------------------------------------------------------------------------

def test_record_log_torn_tail(tmp_path):
    from repro.checkpoint.io import append_record, read_records
    path = str(tmp_path / "rec.log")
    assert read_records(path) == []
    append_record(path, b"first")
    append_record(path, b"second")
    assert read_records(path) == [b"first", b"second"]
    # a crash mid-append leaves a torn tail: drop it, keep the prefix
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 100, 0) + b"partial")
    assert read_records(path) == [b"first", b"second"]


def test_wirelog_replays_one_round(tmp_path):
    wlog = fr.WireLog(str(tmp_path / "wire.log"))
    for t in (1, 1, 2):
        wlog.append(fr.encode_frame(fr.Frame(
            kind=fr.UPLOAD, round=t, client_ids=[t * 10],
            payload=b"p")))
    wlog.append(fr.encode_frame(fr.Frame(kind=fr.TRAIN, round=1)))
    got = wlog.replay(1)
    assert [list(f.client_ids) for f in got] == [[10], [10]]
    assert all(f.kind == fr.UPLOAD for f in got)
    assert wlog.replay(3) == []


# ---------------------------------------------------------------------------
# transport fault domain
# ---------------------------------------------------------------------------

def test_transport_fault_deterministic_and_attempt_keyed():
    cfg = FaultConfig(transport_drop=0.5, transport_corrupt=0.3)
    fm = FaultModel(cfg, 0, 4)
    draws = [fm.transport_fault(wave=2, pod=1, attempt=0)
             for _ in range(5)]
    assert len(set(draws)) == 1  # pure function of the key
    over_attempts = {fm.transport_fault(2, 1, a) for a in range(40)}
    assert len(over_attempts) > 1  # a retry is a fresh draw
    quiet = FaultModel(FaultConfig(), 0, 4)
    assert all(quiet.transport_fault(w, p, 0) is None
               for w in range(10) for p in range(4))
    always = FaultModel(FaultConfig(transport_drop=1.0), 0, 4)
    assert always.transport_fault(0, 0, 0) == "drop"


def test_corrupt_frame_flips_bytes_deterministically():
    cfg = FaultConfig(transport_corrupt=1.0)
    fm = FaultModel(cfg, 0, 4)
    data = bytes(range(64))
    a = fm.corrupt_frame(1, 0, 0, data)
    assert a == fm.corrupt_frame(1, 0, 0, data)
    assert a != data and len(a) == len(data)
    assert a != fm.corrupt_frame(1, 0, 1, data)


def test_transport_knobs_do_not_arm_param_defenses():
    cfg = FaultConfig(transport_drop=0.5)
    assert cfg.transport_enabled and not cfg.enabled
    assert FaultConfig(nan_rate=0.1).enabled
    with pytest.raises(ValueError, match="transport_drop"):
        FaultConfig(transport_drop=1.5).validate()
    with pytest.raises(ValueError, match="transport_delay_s"):
        FaultConfig(transport_delay_s=-1.0).validate()


# ---------------------------------------------------------------------------
# driver: degenerate bit-identity
# ---------------------------------------------------------------------------

def test_registry_has_distributed():
    from repro.drivers import DistributedDriver, available_drivers
    assert "distributed" in available_drivers()
    with pytest.raises(ValueError, match="staleness"):
        DistributedDriver(staleness=1)


def test_shard_clients_partition():
    shards = shard_clients([0, 1, 2, 3, 4, 7], 3)
    assert shards == [[0, 3], [1, 4, 7], [2]]
    assert shard_clients([], 2) == [[], []]


@pytest.mark.parametrize("strategy", ["fedavg", "feddf"])
def test_degenerate_matches_sync(problem, strategy):
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16,))
    kw = dict(source=src) if strategy == "feddf" else {}
    ref = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(strategy), driver="sync", **kw)
    got = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(strategy, dist=DistConfig(n_pods=2)),
                     driver="distributed", **kw)
    _assert_same_run(ref, got)


def test_pod_count_invariance(problem):
    train, val, test, parts, src = problem
    net = mlp(2, 3, hidden=(16,))
    runs = [run_rounds([net], [0] * 6, train, parts, val, test,
                       small_cfg(dist=DistConfig(n_pods=n)),
                       driver="distributed")
            for n in (1, 3)]
    _assert_same_run(runs[0], runs[1])


def test_heterogeneous_degenerate_matches_sync(problem):
    train, val, test, parts, src = problem
    nets = [mlp(2, 3, hidden=(16,)), mlp(2, 3, hidden=(8, 8))]
    proto = [0, 1, 0, 1, 0, 1]
    ref = run_rounds(nets, proto, train, parts, val, test,
                     small_cfg("feddf"), source=src, heterogeneous=True,
                     driver="sync")
    got = run_rounds(nets, proto, train, parts, val, test,
                     small_cfg("feddf", dist=DistConfig(n_pods=2)),
                     source=src, heterogeneous=True, driver="distributed")
    _assert_same_run(ref, got)


def test_low_bit_codec_runs_close(problem):
    train, val, test, parts, _ = problem
    net = mlp(2, 3, hidden=(16,))
    ref = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(), driver="sync")
    got = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(dist=DistConfig(n_pods=2,
                                               wire_codec="int8")),
                     driver="distributed")
    for x in jax.tree.leaves(got[1][0]):
        assert np.isfinite(np.asarray(x)).all()
    drift = abs(got[0][0].final_acc - ref[0][0].final_acc)
    assert drift <= 0.2  # lossy uplink, same problem: stays in range
    # telemetry: int8 uplink is measurably smaller than the downlink
    log = got[0][0].logs[-1]
    assert 0 < log.wire_bytes_up < log.wire_bytes_down


# ---------------------------------------------------------------------------
# driver: robustness ladder
# ---------------------------------------------------------------------------

def test_pod_kill_reroutes_and_trajectory_holds(problem):
    train, val, test, parts, _ = problem
    net = mlp(2, 3, hidden=(16,))
    ref = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(), driver="sync")
    got = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(dist=DistConfig(
                         n_pods=2, heartbeat_s=0.05,
                         upload_deadline_s=0.5,
                         kill_pod=1, kill_after_round=1)),
                     driver="distributed")
    # a killed pod trains but never uploads: recovery flows through the
    # deadline + heartbeat liveness, and re-trained clients are
    # deterministic, so the trajectory is unchanged
    _assert_same_run(ref, got)
    logs = got[0][0].logs
    assert sum(l.n_deadline_misses for l in logs) >= 1
    assert logs[-1].n_pods_alive == 1


def test_crc_retry_keeps_trajectory(problem):
    train, val, test, parts, _ = problem
    net = mlp(2, 3, hidden=(16,))
    ref = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(), driver="sync")
    got = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(dist=DistConfig(n_pods=2),
                               faults=FaultConfig(transport_corrupt=0.2,
                                                  retries=6)),
                     driver="distributed")
    # every corrupted frame is caught by the CRC and re-dispatched with
    # a fresh fault draw — the fused parameters never see garbage
    _assert_same_run(ref, got)
    logs = got[0][0].logs
    assert sum(l.n_crc_failures for l in logs) > 0
    assert sum(l.n_wire_retries for l in logs) > 0


def test_quorum_shortfall_freezes_globals(problem):
    train, val, test, parts, _ = problem
    net = mlp(2, 3, hidden=(16,))
    init = net.init(jax.random.PRNGKey(0))
    results, globals_, _ = run_rounds(
        [net], [0] * 6, train, parts, val, test,
        small_cfg(dist=DistConfig(n_pods=2, upload_deadline_s=0.2),
                  faults=FaultConfig(transport_drop=1.0, quorum=0.5,
                                     retries=1, backoff=1.0)),
        driver="distributed", init_globals=[init])
    logs = results[0].logs
    assert all(l.fused is False for l in logs)
    assert all(l.n_wire_lost > 0 for l in logs)
    # below quorum every round: the globals never move
    for x, y in zip(jax.tree.leaves(init), jax.tree.leaves(globals_[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fusion_pod_restart_replays_wire_log(problem, tmp_path):
    train, val, test, parts, _ = problem
    net = mlp(2, 3, hidden=(16,))
    wl = str(tmp_path / "wire.log")
    snap = {}

    def hook(t, globals_, state, logs, rtt):
        if t == 1:
            snap.update(globals_=list(globals_), state=state,
                        logs=[list(g) for g in logs])

    cfg = lambda: small_cfg(rounds=3, dist=DistConfig(n_pods=2,
                                                      wire_log=wl))
    full = run_rounds([net], [0] * 6, train, parts, val, test, cfg(),
                      driver="distributed", round_end_hook=hook)
    resumed = run_rounds([net], [0] * 6, train, parts, val, test, cfg(),
                         driver="distributed",
                         init_globals=snap["globals_"],
                         init_state=snap["state"],
                         init_logs=snap["logs"], start_round=2)
    _assert_same_run(full, resumed)
    # the restarted round re-dispatched nothing: its uploads came off
    # the wire log (zero uplink bytes on the wire)
    assert resumed[0][0].logs[1].wire_bytes_up == 0
    assert resumed[0][0].logs[2].wire_bytes_up > 0  # next round is live


def test_undefended_crc_off_accepts_garbage(problem):
    train, val, test, parts, _ = problem
    net = mlp(2, 3, hidden=(16,))
    got = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(dist=DistConfig(n_pods=2,
                                               verify_crc=False),
                               faults=FaultConfig(transport_corrupt=0.9)),
                     driver="distributed")
    ref = run_rounds([net], [0] * 6, train, parts, val, test,
                     small_cfg(), driver="sync")
    # with the CRC off the corrupted frames fuse; the run completes but
    # the trajectory visibly departs from the clean one
    assert [l.test_acc for l in got[0][0].logs] != \
        [l.test_acc for l in ref[0][0].logs] or not all(
            np.isfinite(np.asarray(x)).all()
            for x in jax.tree.leaves(got[1][0]))


# ---------------------------------------------------------------------------
# spec + experiment + CLI surface
# ---------------------------------------------------------------------------

def test_dist_spec_validation_and_round_trip():
    from repro.api import DistSpec, ExperimentSpec
    spec = ExperimentSpec()
    spec.dist = DistSpec(transport="loopback", wire_codec="binarize",
                         n_pods=3, heartbeat_s=0.5, upload_deadline_s=2.0)
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec and again.dist.n_pods == 3
    for bad in (DistSpec(transport="carrier-pigeon"),
                DistSpec(wire_codec="fp64"),
                DistSpec(n_pods=0),
                DistSpec(heartbeat_s=0.0),
                DistSpec(upload_deadline_s=-1.0)):
        spec.dist = bad
        with pytest.raises(ValueError, match="dist\\."):
            spec.validate()
    with pytest.raises(ValueError, match="unknown field"):
        DistSpec.from_dict({"transport": "tcp", "kill_pod": 1})


def test_faultspec_mirrors_faultconfig_fields():
    from repro.api import FaultSpec
    spec_fields = {f.name for f in dataclasses.fields(FaultSpec)}
    cfg_fields = {f.name for f in dataclasses.fields(FaultConfig)}
    # spec.validate() round-trips FaultSpec through FaultConfig, so the
    # two layers must never drift apart
    assert spec_fields == cfg_fields


def test_dist_summary_section(problem):
    from repro.api import (DistSpec, DriverSpec, Experiment,
                           ExperimentSpec, FusionSpec, PartitionSpec,
                           StrategySpec, TaskSpec)

    def mk(kind):
        return ExperimentSpec(
            task=TaskSpec(name="blobs", n_samples=400),
            partition=PartitionSpec(n_clients=4, alpha=1.0),
            strategy=StrategySpec(name="fedavg", fusion=FusionSpec(
                max_steps=40, patience=40, eval_every=20, batch_size=32)),
            driver=DriverSpec(kind=kind), dist=DistSpec(n_pods=2),
            rounds=2, client_fraction=0.5, local_epochs=2, seed=0)

    dist = Experiment(mk("distributed")).run().summary()
    assert dist["dist"]["bytes_up"] > 0
    assert dist["dist"]["bytes_down"] > 0
    assert dist["dist"]["min_pods_alive"] == 2
    sync = Experiment(mk("sync")).run().summary()
    assert "dist" not in sync  # historic shapes stay intact


def test_cli_flags_compile_and_round_trip(tmp_path):
    from repro.api import ExperimentSpec
    from repro.launch.train import build_parser, spec_from_args
    args = build_parser().parse_args([
        "--driver", "distributed", "--transport", "loopback",
        "--wire-codec", "int8", "--n-pods", "3",
        "--heartbeat-s", "0.5", "--upload-deadline-s", "2.5",
        "--wire-log", "w.log", "--faults-transport-corrupt", "0.05",
        "--faults-transport-drop", "0.01", "--rounds", "2"])
    spec = spec_from_args(args)
    assert spec.driver.kind == "distributed"
    assert spec.dist.transport == "loopback"
    assert spec.dist.wire_codec == "int8" and spec.dist.n_pods == 3
    assert spec.dist.heartbeat_s == 0.5
    assert spec.dist.upload_deadline_s == 2.5
    assert spec.dist.verify_crc is True and spec.dist.wire_log == "w.log"
    assert spec.faults.transport_corrupt == 0.05
    assert spec.faults.transport_drop == 0.01
    spec.validate()
    # --dump-config -> --config round trip is lossless
    path = str(tmp_path / "spec.json")
    spec.save(path)
    assert ExperimentSpec.load(path) == spec
    undef = spec_from_args(build_parser().parse_args(["--no-verify-crc"]))
    assert undef.dist.verify_crc is False


def test_tcp_transport_end_to_end():
    """Real subprocess pods over localhost TCP, bit-identical to sync."""
    from repro.api import (DistSpec, DriverSpec, Experiment,
                           ExperimentSpec, FusionSpec, PartitionSpec,
                           StrategySpec, TaskSpec)

    def mk(kind, dist=None):
        return ExperimentSpec(
            task=TaskSpec(name="blobs", n_samples=400),
            partition=PartitionSpec(n_clients=4, alpha=1.0),
            strategy=StrategySpec(name="fedavg", fusion=FusionSpec(
                max_steps=40, patience=40, eval_every=20, batch_size=32)),
            driver=DriverSpec(kind=kind), dist=dist or DistSpec(),
            rounds=2, client_fraction=0.5, local_epochs=2, seed=0)

    ref = Experiment(mk("sync")).run()
    got = Experiment(mk("distributed", DistSpec(
        transport="tcp", n_pods=2, upload_deadline_s=300.0))).run()
    assert [l.test_acc for l in got.results[0].logs] == \
        [l.test_acc for l in ref.results[0].logs]
    for x, y in zip(jax.tree.leaves(ref.global_params[0]),
                    jax.tree.leaves(got.global_params[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_tcp_transport_refused_when_process_holds_an_accelerator(
        monkeypatch):
    """An accelerator belongs to one process: with the parent on a TPU the
    tcp transport must refuse before starting any pod, naming the
    one-host transport."""
    from repro.dist.driver import DistributedDriver
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dcfg = DistConfig(transport="tcp", spec_json="{}")
    with pytest.raises(RuntimeError, match="loopback"):
        DistributedDriver()._start_pods(None, dcfg)
