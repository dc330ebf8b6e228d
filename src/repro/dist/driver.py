"""The ``distributed`` driver: a fusion pod coordinating client pods.

The fusion pod owns everything the sync driver's loop owns — cohort
sampling (the sole rng consumer), ``fault_pipeline``, ``aggregate`` (and
with it the logit bank), ``guard_globals``, ``evaluate_round`` and the
checkpoint hook — while client training happens in client pods behind
the wire protocol of ``repro.dist.frames``:

    sample_cohort -> shard cohort over pods -> TRAIN frames (fp32
    globals downlink) -> collect UPLOAD frames (configured codec)
    against per-attempt deadlines -> assemble stacks in original cohort
    order -> fault_pipeline -> quorum -> aggregate -> guard -> evaluate

Robustness ladder, outermost first (docs/distributed.md has the
failure-matrix table):

- **CRC / version check** on every frame; a checksum failure triggers a
  re-dispatch with ``attempt + 1`` (a fresh fault draw, PR 8 semantics),
  and exhausted retries escalate to quarantine (``sampler.penalize``).
- **Per-upload deadlines** ``upload_deadline_s * backoff ** attempt``;
  a miss re-dispatches the missing clients to the request's pod if it
  still looks alive, else to the next live pod.
- **Heartbeat liveness**: a pod silent for ``3 * heartbeat_s`` is
  presumed dead; its clients re-route at dispatch time (per-client
  training is grouping-independent, so re-routing never changes the
  trajectory).
- **Quorum degradation**: wire losses count against
  ``faults.quorum`` exactly like screened-out uploads — below quorum
  the round skips fusion and carries frozen globals (sync semantics).
- **Wire log + atomic checkpoints**: accepted UPLOAD frames append to
  ``dist.wire_log``; a restarted fusion pod replays the resumed round's
  uploads instead of re-dispatching them.

The degenerate config — loopback transport, fp32 codec, zero fault
rates — is bit-identical to the ``sync`` driver (pinned in
``tests/test_dist.py``): every phase below is the same deterministic
function of the same inputs, and the wire round-trips are exact.
"""
from __future__ import annotations

import heapq
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core.engine import _UNSET, RoundEngine
from repro.dist import frames as fr
from repro.dist.config import DistConfig
from repro.dist.pods import ClientPodRunner, shard_clients
from repro.dist.transport import LoopbackTransport, TCPTransport
from repro.drivers.base import Driver, register_driver
from repro.obs import trace as _trace

# byte offset of the frame-kind field (magic + u16 version), used to
# classify a possibly-corrupted frame without decoding it
_KIND_OFF = len(fr.MAGIC) + 2


class _Runtime:
    """Pods + transport + cross-round liveness state of one run."""

    def __init__(self, transport, n_pods: int):
        self.transport = transport
        self.n_pods = n_pods
        now = time.monotonic()
        self.last_seen: Dict[int, float] = {j: now for j in range(n_pods)}
        self.runners: List[ClientPodRunner] = []  # loopback only
        self.procs: List[subprocess.Popen] = []   # tcp only
        self.tmpdir: Optional[str] = None

    def close(self) -> None:
        for j in range(self.n_pods):
            try:
                self.transport.send(j, fr.encode_frame(
                    fr.Frame(kind=fr.SHUTDOWN)))
            except Exception:
                pass
        for r in self.runners:
            r.kill()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        self.transport.close()
        if self.tmpdir is not None:
            import shutil
            shutil.rmtree(self.tmpdir, ignore_errors=True)


@register_driver("distributed")
class DistributedDriver(Driver):
    """Fusion pod + client pods behind the versioned wire protocol."""

    def __init__(self, staleness: int = 0, prefetch: int = 1):
        if staleness != 0:
            raise ValueError(
                f"{type(self).__name__} runs sync-quorum semantics; "
                f"staleness={staleness} only applies to the "
                f"async_pipelined driver")
        super().__init__(staleness=staleness, prefetch=prefetch)

    # -- pod lifecycle ----------------------------------------------------

    def _start_pods(self, engine: RoundEngine, dcfg: DistConfig) -> _Runtime:
        if dcfg.transport == "loopback":
            transport = LoopbackTransport(dcfg.n_pods)
            rt = _Runtime(transport, dcfg.n_pods)
            # one process, one device: serialize the pods' jax work
            lock = threading.Lock()
            rt.runners = [
                ClientPodRunner(engine, j, transport.endpoint(j),
                                heartbeat_s=dcfg.heartbeat_s,
                                lock=lock).start()
                for j in range(dcfg.n_pods)]
            return rt
        if dcfg.spec_json is None:
            raise ValueError(
                "dist.transport='tcp' needs dist.spec_json (run through "
                "the Experiment/spec API so client pods can rebuild the "
                "engine)")
        backend = jax.default_backend()
        if backend != "cpu":
            # an accelerator belongs to one process at a time, and this
            # one already holds it: a child pod would fail or hang
            raise RuntimeError(
                f"dist.transport='tcp' starts one JAX process per pod, but "
                f"this process already holds the {backend} device; use "
                f"dist.transport='loopback', the one-host transport")
        transport = TCPTransport()
        rt = _Runtime(transport, dcfg.n_pods)
        rt.tmpdir = tempfile.mkdtemp(prefix="repro_dist_")
        spec_path = os.path.join(rt.tmpdir, "spec.json")
        with open(spec_path, "w") as f:
            f.write(dcfg.spec_json)
        src_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = src_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        for j in range(dcfg.n_pods):
            rt.procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.dist.pods",
                 "--spec", spec_path, "--pod", str(j),
                 "--host", transport.host, "--port", str(transport.port),
                 "--heartbeat-s", str(dcfg.heartbeat_s)],
                env=env))
        transport.accept(dcfg.n_pods, timeout=300.0)
        now = time.monotonic()
        for j in range(dcfg.n_pods):
            rt.last_seen[j] = now
        return rt

    # -- the loop ---------------------------------------------------------

    def run(self, engine: RoundEngine, *, log_fn=None, init_globals=None,
            init_state=_UNSET, start_round=1, init_logs=None,
            round_end_hook=None):
        dcfg: DistConfig = engine.cfg.dist
        dcfg.validate()
        codec = fr.get_codec(dcfg.wire_codec)
        faults = engine.cfg.faults
        wire_fm = None
        if faults.transport_enabled:
            from repro.population.faults import FaultModel
            wire_fm = FaultModel(faults, engine.cfg.seed, dcfg.n_pods)
        wlog = fr.WireLog(dcfg.wire_log) if dcfg.wire_log else None

        globals_, state, logs, rng = self._setup(
            engine, init_globals, init_state, init_logs, start_round)
        rounds_to_target = None
        rt = self._start_pods(engine, dcfg)
        try:
            for t in range(start_round, engine.cfg.rounds + 1):
                active = engine.sample_cohort(rng)
                received, st = self._collect(
                    engine, t, active, globals_, codec, wire_fm, dcfg,
                    wlog, rt, replay=(t == start_round))
                groups, ids_by_proto = self._assemble(
                    engine, active, received, globals_)
                fstats = engine.fault_pipeline(t, groups, ids_by_proto)
                # wire losses count against quorum exactly like screened
                # uploads: dispatched is the full cohort, not survivors
                qstats = fstats
                if qstats is not None:
                    qstats["dispatched"] = len(active)
                elif st["wire_lost"]:
                    qstats = {"dispatched": len(active),
                              "kept": len(active) - st["wire_lost"]}
                fuse = engine.quorum_met(qstats)
                prev = list(globals_)
                if fuse:
                    globals_, state, infos, dropped, ens_acc = \
                        engine.aggregate(t, groups, state)
                    globals_, rolled = engine.guard_globals(globals_, prev)
                else:  # quorum shortfall: carry the globals, skip fusion
                    infos = [{} for _ in range(engine.n_proto)]
                    dropped = [0] * engine.n_proto
                    ens_acc = None
                    rolled = [False] * engine.n_proto
                round_logs = engine.evaluate_round(t, globals_, groups,
                                                   infos, dropped, ens_acc)
                n_alive = sum(
                    1 for j in range(dcfg.n_pods) if self._alive(rt, j, dcfg))
                for p, log in enumerate(round_logs):
                    if fstats is not None:
                        log.n_corrupted = fstats["corrupted"]
                        log.n_quarantined = fstats["quarantined"]
                        log.n_retries = fstats["retries"]
                        log.rolled_back = bool(log.rolled_back or rolled[p])
                    if fstats is not None or qstats is not None:
                        log.fused = fuse
                    log.wire_bytes_up = st["bytes_up"]
                    log.wire_bytes_down = st["bytes_down"]
                    log.n_wire_retries = st["wire_retries"]
                    log.n_crc_failures = st["crc_failures"]
                    log.n_deadline_misses = st["deadline_misses"]
                    log.n_wire_lost = st["wire_lost"]
                    log.n_pods_alive = n_alive
                reached, stop_requested = self._emit_round(
                    engine, t, round_logs, logs, log_fn)
                if reached:
                    rounds_to_target = t

                if round_end_hook is not None:
                    round_end_hook(t, globals_, state, logs,
                                   rounds_to_target)

                if rounds_to_target is not None or stop_requested:
                    break
        finally:
            rt.close()

        return self._results(engine, logs, globals_, rounds_to_target)

    # -- liveness ---------------------------------------------------------

    @staticmethod
    def _alive(rt: _Runtime, pod: int, dcfg: DistConfig) -> bool:
        return (time.monotonic() - rt.last_seen[pod]
                <= max(3.0 * dcfg.heartbeat_s, 0.05))

    # -- wire collection --------------------------------------------------

    def _collect(self, engine: RoundEngine, t: int, active, globals_,
                 codec, wire_fm, dcfg: DistConfig, wlog, rt: _Runtime, *,
                 replay: bool):
        """Dispatch TRAIN frames and gather UPLOADs for round ``t``.

        Returns ``(received, stats)`` where ``received`` maps client id
        -> decoded flat leaf list and ``stats`` is the round's wire
        telemetry.
        """
        import jax

        from repro.obs.metrics import REGISTRY

        faults = engine.cfg.faults
        proto = engine.client_proto
        active_set = {int(k) for k in active}
        tmpl = [[np.asarray(l) for l in jax.tree.leaves(globals_[p])]
                for p in range(engine.n_proto)]
        received: Dict[int, List[np.ndarray]] = {}
        st = {k: 0 for k in (
            "bytes_up", "bytes_down", "crc_failures", "deadline_misses",
            "wire_retries", "wire_lost", "frames", "replayed",
            "dispatches")}

        def store_upload(frame: fr.Frame) -> int:
            """Decode an accepted UPLOAD into ``received``; returns the
            number of newly covered clients."""
            c = fr.codec_by_id(frame.codec_id)
            blobs = fr.unpack_blobs(frame.payload, len(frame.client_ids))
            fresh = 0
            for k, blob in zip(frame.client_ids, blobs):
                k = int(k)
                if k in active_set and k not in received:
                    received[k] = c.decode(blob, tmpl[proto[k]])
                    fresh += 1
            return fresh

        # -- fusion-pod restart: replay this round's logged uploads ------
        if replay and wlog is not None:
            with _trace.span("wire_replay", round=int(t)) as sp:
                for frame in wlog.replay(t):
                    try:
                        st["replayed"] += store_upload(frame)
                    except fr.FrameError:
                        continue
                sp.annotate(replayed=st["replayed"])
            REGISTRY.counter("dist.wirelog_replayed").add(st["replayed"])

        # -- downlink: all prototypes' globals, always fp32 (exact) ------
        fp32 = fr.get_codec("fp32")
        down_payload = fr.pack_blobs(
            [fp32.encode(tmpl[p]) for p in range(engine.n_proto)])

        reqs: Dict[int, dict] = {}
        next_rid = [0]
        dark: set = set()  # pods disconnect-faulted for this round

        def alive(j: int) -> bool:
            return j not in dark and self._alive(rt, j, dcfg)

        def pick_pod(home: int) -> Optional[int]:
            for j in [home] + [j for j in range(dcfg.n_pods) if j != home]:
                if alive(j):
                    return j
            return None

        def dispatch(ids: List[int], pod: int, attempt: int) -> None:
            rid = next_rid[0]
            next_rid[0] += 1
            data = fr.encode_frame(fr.Frame(
                kind=fr.TRAIN, round=t, wave=t, client_ids=ids,
                codec_id=codec.codec_id,
                meta={"req": rid, "attempt": attempt, "codec": codec.name},
                payload=down_payload))
            with _trace.span("wire_dispatch", round=int(t)) as sp:
                sp.annotate(pod=pod, attempt=attempt, n_clients=len(ids),
                            nbytes=len(data))
                rt.transport.send(pod, data)
            st["bytes_down"] += len(data)
            st["dispatches"] += 1
            deadline = time.monotonic() + (
                dcfg.upload_deadline_s * (faults.backoff ** attempt))
            reqs[rid] = {"pod": pod, "ids": list(ids), "attempt": attempt,
                         "deadline": deadline}

        def give_up(missing: List[int], why: str) -> None:
            st["wire_lost"] += len(missing)
            if why == "crc":
                # CRC-failure escalation: retries exhausted on a
                # corrupting link -> quarantine the clients' uploads
                engine.sampler.penalize([int(k) for k in missing], 0.5)

        def retry(rid: int, why: str) -> None:
            r = reqs.pop(rid, None)
            if r is None:
                return
            missing = [k for k in r["ids"] if k not in received]
            if not missing:
                return
            attempt = r["attempt"] + 1
            if attempt > faults.retries:
                give_up(missing, why)
                return
            # prefer the request's pod while it still heartbeats, else
            # the next live pod (re-routing never changes the trajectory:
            # per-client training is grouping-independent)
            target = pick_pod(r["pod"])
            if target is None:
                give_up(missing, why)
                return
            st["wire_retries"] += 1
            REGISTRY.counter("dist.wire_retries").add(1)
            dispatch(missing, target, attempt)

        def oldest_req_of(pod: int) -> Optional[int]:
            rids = [rid for rid, r in reqs.items() if r["pod"] == pod]
            return min(rids) if rids else None

        with _trace.span("wire_collect", round=int(t)) as sp:
            for home, ids in enumerate(shard_clients(
                    [k for k in active_set if k not in received],
                    dcfg.n_pods)):
                if not ids:
                    continue
                target = pick_pod(home)
                if target is None:
                    give_up(ids, "dead")
                    continue
                dispatch(sorted(ids), target, 0)

            # chaos hook: crash a pod right after this round's dispatch —
            # the killed pod trains but never uploads, and recovery must
            # flow through deadline + heartbeat-liveness re-routing
            if (rt.runners and dcfg.kill_pod is not None
                    and t == dcfg.kill_after_round
                    and 0 <= dcfg.kill_pod < len(rt.runners)):
                rt.runners[dcfg.kill_pod].kill()

            delayed: list = []  # (release_time, seq, pod, data)
            seq = 0
            while reqs:
                now = time.monotonic()
                msg = None
                if delayed and delayed[0][0] <= now:
                    _, _, pod, data = heapq.heappop(delayed)
                    msg, preprocessed = (pod, data), True
                else:
                    got = rt.transport.recv(0.05)
                    if got is not None:
                        msg, preprocessed = got, False
                if msg is not None:
                    pod, data = msg
                    rt.last_seen[pod] = time.monotonic()
                    st["frames"] += 1
                    is_upload = (len(data) > _KIND_OFF
                                 and data[_KIND_OFF] == fr.UPLOAD)
                    if is_upload and wire_fm is not None and not preprocessed:
                        req = oldest_req_of(pod)
                        attempt = reqs[req]["attempt"] if req is not None else 0
                        fault = wire_fm.transport_fault(t, pod, attempt)
                        if fault == "disconnect":
                            dark.add(pod)
                            continue  # frame lost; deadline re-routes
                        if fault == "drop":
                            continue
                        if fault == "corrupt":
                            data = wire_fm.corrupt_frame(t, pod, attempt,
                                                         data)
                        elif fault == "delay":
                            heapq.heappush(
                                delayed,
                                (now + faults.transport_delay_s, seq, pod,
                                 data))
                            seq += 1
                            continue
                    try:
                        frame = fr.decode_frame(
                            data, verify_crc=dcfg.verify_crc)
                    except fr.CRCError:
                        st["crc_failures"] += 1
                        REGISTRY.counter("dist.crc_failures").add(1)
                        rid = oldest_req_of(pod)
                        if rid is not None:
                            retry(rid, "crc")
                        continue
                    except fr.FrameError:
                        rid = oldest_req_of(pod)
                        if rid is not None:
                            retry(rid, "crc")
                        continue
                    if frame.kind == fr.HEARTBEAT:
                        continue
                    if frame.kind != fr.UPLOAD or frame.round != t:
                        continue  # stale round / unexpected kind
                    try:
                        store_upload(frame)
                    except (fr.FrameError, ValueError):
                        # structurally broken payload (possible with
                        # verify_crc off): treat like a checksum failure
                        st["crc_failures"] += 1
                        rid = oldest_req_of(pod)
                        if rid is not None:
                            retry(rid, "crc")
                        continue
                    st["bytes_up"] += len(data)
                    if wlog is not None:
                        wlog.append(data)
                    for rid in list(reqs):
                        if all(k in received for k in reqs[rid]["ids"]):
                            del reqs[rid]
                # deadline sweep
                now = time.monotonic()
                for rid in [r for r in list(reqs)
                            if reqs[r]["deadline"] <= now]:
                    st["deadline_misses"] += 1
                    REGISTRY.counter("dist.deadline_misses").add(1)
                    retry(rid, "deadline")
            sp.annotate(**st)

        REGISTRY.counter("dist.train_dispatches").add(st["dispatches"])
        REGISTRY.counter("dist.bytes_up").add(st["bytes_up"])
        REGISTRY.counter("dist.bytes_down").add(st["bytes_down"])
        REGISTRY.gauge("dist.pods_alive").set(sum(
            1 for j in range(dcfg.n_pods) if self._alive(rt, j, dcfg)))
        return received, st

    # -- stack assembly ---------------------------------------------------

    def _assemble(self, engine: RoundEngine, active, received, globals_):
        """Received leaf lists -> per-prototype GroupRounds in the
        cohort's original order — the exact inputs ``sync``'s
        ``train_clients`` would produce for the surviving clients."""
        import jax
        import jax.numpy as jnp

        from repro.core.strategies import GroupRound

        proto = engine.client_proto
        by_proto: List[List[int]] = [[] for _ in range(engine.n_proto)]
        for k in active:
            if int(k) in received:
                by_proto[proto[int(k)]].append(int(k))
        groups, ids_by_proto = [], []
        for p in range(engine.n_proto):
            ks = by_proto[p]
            if not ks:
                groups.append(GroupRound(engine.nets[p], globals_[p], None,
                                         np.zeros(0)))
                ids_by_proto.append(None)
                continue
            flat_t, treedef = jax.tree.flatten(globals_[p])
            stack = jax.tree.unflatten(treedef, [
                jnp.asarray(np.stack([received[k][li] for k in ks]))
                for li in range(len(flat_t))])
            weights = np.array([float(len(engine.parts[k])) for k in ks])
            groups.append(GroupRound(engine.nets[p], globals_[p], stack,
                                     weights))
            ids_by_proto.append(ks)
        return groups, ids_by_proto
