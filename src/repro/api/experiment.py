"""`Experiment` — the unified run/resume entry point over the round engine.

One facade subsumes both historic drivers: a one-prototype cohort runs
Algorithm 1 exactly as ``run_federated`` did, a multi-prototype cohort
runs Algorithm 3 exactly as ``run_federated_heterogeneous`` did (same
seeds, same batch streams, same aggregation — the equivalence is pinned
by ``tests/test_experiment_api.py``), and both return one
:class:`RunResult`.

Observation is typed: instead of the historic ``log_fn`` whose payload
changed shape between the two drivers (``RoundLog`` vs
``(group, RoundLog)``), observers receive a :class:`RoundEvent` in both
cases.

Resume: ``Experiment.run(checkpoint_dir=...)`` writes the spec plus
per-round snapshots (globals per prototype, server-strategy state,
round logs) through ``checkpoint/io.py``; ``Experiment.resume(dir)``
rebuilds everything from the spec, reloads the latest snapshot and
continues — the engine replays the cohort-sampling rng for completed
rounds, so the resumed trajectory is identical to an uninterrupted run.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.api.registries import (TaskBundle, get_model, get_quantizer,
                                  get_source, get_task)
from repro.api.spec import ExperimentSpec
from repro.checkpoint import io as ckpt
from repro.core.engine import (_UNSET, FLConfig, FLResult, RoundLog,
                               run_rounds)
from repro.core.feddf import FusionConfig
from repro.core.nets import Net
from repro.dist.config import DistConfig
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import Dataset, train_val_test_split
from repro.obs import trace as _trace
from repro.obs.metrics import CSVSink, JSONLSink, MetricsObserver
from repro.population.config import (FaultConfig, PopulationConfig,
                                     TrafficConfig)


@dataclasses.dataclass
class RoundEvent:
    """One prototype group's per-round observation, uniform across
    homogeneous and heterogeneous runs (group is 0 for the former).

    An observer may call :meth:`request_stop` to end the run after the
    current round — the per-round eval seam for custom early-stopping
    criteria beyond ``target_accuracy`` (which the engine itself checks,
    for heterogeneous cohorts too).  Observer stops are soft: they do not
    set ``rounds_to_target``, and a checkpointed run resumes past them.
    """

    round: int
    group: int
    n_groups: int
    heterogeneous: bool
    log: RoundLog
    stop_requested: bool = dataclasses.field(default=False, compare=False)

    def request_stop(self) -> None:
        self.stop_requested = True


Observer = Callable[[RoundEvent], None]


@dataclasses.dataclass
class RunResult:
    """Unified result: one :class:`FLResult` per prototype group."""

    spec: ExperimentSpec
    results: List[FLResult]
    global_params: List[dict]
    rounds_to_target: Optional[int]
    net_names: List[str]
    #: flight-recorder summary (phase totals, per-round breakdown, async
    #: idle gap) — set only when the run was traced (spec.obs / ObsSpec)
    obs: Optional[dict] = None

    @property
    def heterogeneous(self) -> bool:
        return len(self.results) > 1

    @property
    def result(self) -> FLResult:
        """The single group's result (homogeneous convenience)."""
        if self.heterogeneous:
            raise ValueError("heterogeneous run: use .results[group]")
        return self.results[0]

    @property
    def final_acc(self) -> float:
        return max(r.final_acc for r in self.results)

    @property
    def best_acc(self) -> float:
        return max(r.best_acc for r in self.results)

    @staticmethod
    def _bank_summary(logs) -> dict:
        """Last round's bank observables (decision / storage dtype /
        device bytes) — how the quantized-bank memory saving surfaces in
        summary.json without a debugger."""
        last = logs[-1] if logs else None
        return {"decision": getattr(last, "bank", ""),
                "dtype": getattr(last, "bank_dtype", ""),
                "nbytes": getattr(last, "bank_nbytes", 0)}

    @staticmethod
    def _population_summary(logs) -> Optional[dict]:
        """Aggregate buffered-async population telemetry, or None for
        runs that never set it (sync / async drivers)."""
        plogs = [l for l in logs
                 if getattr(l, "staleness_hist", None) is not None]
        if not plogs:
            return None
        hist = [0] * max(len(l.staleness_hist) for l in plogs)
        for l in plogs:
            for s, c in enumerate(l.staleness_hist):
                hist[s] += int(c)
        total = sum(hist)
        mean_s = (sum(s * c for s, c in enumerate(hist)) / total
                  if total else 0.0)
        return {
            "uploads_fused": total,
            "mean_staleness": mean_s,
            "staleness_hist": hist,
            "last_buffer_fill": int(plogs[-1].buffer_fill),
            "last_straggling": int(plogs[-1].n_straggling),
            "dropped_uploads": sum(int(l.n_dropped_uploads)
                                   for l in plogs),
            "stale_dropped": sum(int(l.n_stale_dropped) for l in plogs),
            "mean_eff_participants": float(
                np.mean([l.eff_participants for l in plogs])),
        }

    @staticmethod
    def _fault_summary(logs) -> Optional[dict]:
        """Aggregate fault/defense telemetry (docs/robustness.md), or
        None for runs where the fault seam never fired — their
        summary.json keeps the historic shape exactly."""
        corrupted = sum(int(getattr(l, "n_corrupted", 0)) for l in logs)
        quarantined = sum(int(getattr(l, "n_quarantined", 0)) for l in logs)
        retries = sum(int(getattr(l, "n_retries", 0)) for l in logs)
        filtered = sum(int(getattr(l, "n_teachers_filtered", 0))
                       for l in logs)
        skipped = sum(1 for l in logs if not getattr(l, "fused", True))
        rollbacks = sum(1 for l in logs if getattr(l, "rolled_back", False))
        if not (corrupted or quarantined or retries or filtered
                or skipped or rollbacks):
            return None
        return {
            "corrupted_uploads": corrupted,
            "quarantined_uploads": quarantined,
            "retries": retries,
            "teachers_filtered": filtered,
            "rounds_skipped": skipped,
            "rollbacks": rollbacks,
        }

    @staticmethod
    def _dist_summary(logs) -> Optional[dict]:
        """Aggregate wire-protocol telemetry (docs/distributed.md), or
        None for runs that never touched the wire (every other driver) —
        their summary.json keeps the historic shape exactly."""
        bytes_up = sum(int(getattr(l, "wire_bytes_up", 0)) for l in logs)
        bytes_down = sum(int(getattr(l, "wire_bytes_down", 0)) for l in logs)
        if not (bytes_up or bytes_down):
            return None
        return {
            "bytes_up": bytes_up,
            "bytes_down": bytes_down,
            "wire_retries": sum(int(getattr(l, "n_wire_retries", 0))
                                for l in logs),
            "crc_failures": sum(int(getattr(l, "n_crc_failures", 0))
                                for l in logs),
            "deadline_misses": sum(int(getattr(l, "n_deadline_misses", 0))
                                   for l in logs),
            "wire_lost": sum(int(getattr(l, "n_wire_lost", 0))
                             for l in logs),
            "min_pods_alive": min(int(getattr(l, "n_pods_alive", 0))
                                  for l in logs),
        }

    def summary(self) -> dict:
        """Summary dict in the historic ``launch/train.py`` shapes.
        Buffered-async runs additionally carry a ``population`` section
        (docs/population.md) and fault-injected runs a ``faults``
        section (docs/robustness.md); their absence keeps older shapes
        intact."""
        if not self.heterogeneous:
            r = self.results[0]
            out = {"final": r.final_acc, "best": r.best_acc,
                   "rounds_to_target": self.rounds_to_target,
                   "per_round": [l.test_acc for l in r.logs],
                   "bank": self._bank_summary(r.logs)}
            pop = self._population_summary(r.logs)
            if pop is not None:
                out["population"] = pop
            faults = self._fault_summary(r.logs)
            if faults is not None:
                out["faults"] = faults
            dist = self._dist_summary(r.logs)
            if dist is not None:
                out["dist"] = dist
            if self.obs is not None:
                out["obs"] = self.obs
            return out
        out = {f"proto_{g}": {"final": r.final_acc, "best": r.best_acc,
                              "per_round": [l.test_acc for l in r.logs],
                              "bank": self._bank_summary(r.logs)}
               for g, r in enumerate(self.results)}
        pop = self._population_summary(self.results[0].logs)
        if pop is not None:
            out["population"] = pop
        faults = self._fault_summary(
            [l for r in self.results for l in r.logs])
        if faults is not None:
            out["faults"] = faults
        # wire telemetry is round-level (every group's log of round t
        # carries the same counters), so aggregate one group only
        dist = self._dist_summary(self.results[0].logs)
        if dist is not None:
            out["dist"] = dist
        if self.obs is not None:
            out["obs"] = self.obs
        return out


# ---------------------------------------------------------------------------
# spec -> components (the compile step)
# ---------------------------------------------------------------------------

def build_task_bundle(spec: ExperimentSpec) -> TaskBundle:
    seed = spec.task.seed if spec.task.seed is not None else spec.seed
    return get_task(spec.task.name)(
        n_samples=spec.task.n_samples, seed=seed, **spec.task.params)


def build_splits(spec: ExperimentSpec, bundle: TaskBundle
                 ) -> Tuple[Dataset, Dataset, Dataset, List[np.ndarray]]:
    train, val, test = train_val_test_split(bundle.dataset, seed=spec.seed)
    pseed = (spec.partition.seed if spec.partition.seed is not None
             else spec.seed)
    parts = dirichlet_partition(
        train.y, spec.partition.n_clients, spec.partition.alpha, seed=pseed,
        min_per_client=spec.partition.min_per_client)
    return train, val, test, parts


def build_cohort(spec: ExperimentSpec, bundle: TaskBundle
                 ) -> Tuple[List[Net], List[int]]:
    nets = [get_model(m.name)(bundle, **m.params)
            for m in spec.cohort.prototypes]
    return nets, spec.cohort.client_prototypes(spec.partition.n_clients)


def build_source(spec: ExperimentSpec, bundle: TaskBundle, train: Dataset):
    if spec.source is None:
        return None
    return get_source(spec.source.name)(bundle, train, seed=spec.seed,
                                        **spec.source.params)


def to_fl_config(spec: ExperimentSpec) -> FLConfig:
    """Compile the declarative spec into the engine-level config."""
    s = spec.strategy
    quantize = (None if spec.privacy.quantizer is None
                else get_quantizer(spec.privacy.quantizer))
    faults = FaultConfig(**spec.faults.to_dict())
    # tcp client pods rebuild their engine from the serialized spec, so
    # the fusion pod carries it into the config it hands the driver
    dist = DistConfig(
        transport=spec.dist.transport, wire_codec=spec.dist.wire_codec,
        n_pods=spec.dist.n_pods, heartbeat_s=spec.dist.heartbeat_s,
        upload_deadline_s=spec.dist.upload_deadline_s,
        verify_crc=spec.dist.verify_crc, wire_log=spec.dist.wire_log,
        spec_json=(spec.to_json()
                   if spec.dist.transport == "tcp" else None))
    # the distill divergence guard rides the fault axis: a per-chunk
    # finite-ness check + rollback only when faults can actually fire,
    # so fault-free fusions keep the guard-free (bit-identical) path
    fusion = FusionConfig(**s.fusion.to_dict(),
                          divergence_guard=faults.enabled)
    return FLConfig(
        rounds=spec.rounds, client_fraction=spec.client_fraction,
        local_epochs=spec.local_epochs,
        local_batch_size=spec.local_batch_size, local_lr=spec.local_lr,
        strategy=s.name, prox_mu=s.prox_mu,
        server_momentum=s.server_momentum, drop_worst=s.drop_worst,
        trim_frac=s.trim_frac, faults=faults, dist=dist,
        seed=spec.seed, local_optimizer=spec.local_optimizer,
        local_adam_lr=spec.local_adam_lr, quantize=quantize,
        fusion=fusion,
        feddf_init_from=s.feddf_init_from,
        target_accuracy=spec.target_accuracy,
        dp_clip=spec.privacy.clip,
        dp_noise_multiplier=spec.privacy.noise_multiplier,
        population=PopulationConfig(
            size=spec.population.size,
            sampler=spec.population.sampler,
            buffer_size=spec.population.buffer_size,
            max_staleness=spec.population.max_staleness,
            staleness_exponent=spec.population.staleness_exponent,
            traffic=TrafficConfig(**spec.population.traffic.to_dict())))


def build_mesh(spec: ExperimentSpec):
    if not spec.sharding.shard_clients:
        return None
    from repro.launch.mesh import make_client_mesh
    return make_client_mesh()


def build_engine(spec: ExperimentSpec):
    """Compile a validated spec all the way to a :class:`RoundEngine`.

    This is how a tcp client pod (``python -m repro.dist.pods``) rebuilds
    the exact engine the fusion pod runs: the spec is the single source
    of truth, so both sides derive identical data splits, prototypes and
    compiled client updates from it."""
    from repro.core.engine import RoundEngine

    spec = spec.validate()
    bundle = build_task_bundle(spec)
    train, val, test, parts = build_splits(spec, bundle)
    nets, client_proto = build_cohort(spec, bundle)
    source = build_source(spec, bundle, train)
    return RoundEngine(nets, client_proto, train, parts, val, test,
                       to_fl_config(spec), source=source,
                       heterogeneous=len(nets) > 1, mesh=build_mesh(spec),
                       client_axis=spec.sharding.client_axis)


# ---------------------------------------------------------------------------
# checkpoint round-trip helpers
# ---------------------------------------------------------------------------

def _jsonable(o):
    if isinstance(o, (np.bool_,)):
        return bool(o)
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, (np.floating, jax.Array)):
        return float(o)
    return str(o)


def _round_dir(checkpoint_dir: str, t: int) -> str:
    return os.path.join(checkpoint_dir, "rounds", f"{t:05d}")


_KEEP_ROUND_DIRS = 2  # latest + one fallback against partial writes


def _save_round(checkpoint_dir: str, t: int, globals_: List[dict], state,
                logs: List[List[RoundLog]],
                rounds_to_target: Optional[int]) -> None:
    with _trace.span("checkpoint_write", round=int(t)):
        _save_round_body(checkpoint_dir, t, globals_, state, logs,
                         rounds_to_target)


def _save_round_body(checkpoint_dir, t, globals_, state, logs,
                     rounds_to_target) -> None:
    rd = _round_dir(checkpoint_dir, t)
    os.makedirs(rd, exist_ok=True)
    for g, params in enumerate(globals_):
        ckpt.save(os.path.join(rd, f"global_{g}"), params)
    ckpt.save_obj(os.path.join(rd, "state"), state)
    # logs.json is written LAST and atomically: its presence marks the
    # snapshot complete, so a crash mid-checkpoint leaves a dir the
    # loader recognises as partial and skips
    tmp = os.path.join(rd, "logs.json.tmp")
    with open(tmp, "w") as f:
        json.dump({"round": t, "rounds_to_target": rounds_to_target,
                   "logs": [[dataclasses.asdict(l) for l in group]
                            for group in logs]},
                  f, default=_jsonable)
    os.replace(tmp, os.path.join(rd, "logs.json"))
    # resume only ever reads the newest snapshot (it holds the full log
    # history), so prune superseded round dirs instead of accumulating
    # one model copy per round
    rounds_dir = os.path.join(checkpoint_dir, "rounds")
    stale = sorted(e for e in os.listdir(rounds_dir)
                   if e.isdigit())[:-_KEEP_ROUND_DIRS]
    for e in stale:
        shutil.rmtree(os.path.join(rounds_dir, e), ignore_errors=True)


def _load_latest_round(checkpoint_dir: str, nets: List[Net]
                       ) -> Tuple[int, List[dict], object,
                                  List[List[RoundLog]], Optional[int]]:
    rounds_dir = os.path.join(checkpoint_dir, "rounds")
    entries = (sorted(e for e in os.listdir(rounds_dir) if e.isdigit())
               if os.path.isdir(rounds_dir) else [])
    # newest complete snapshot wins; dirs without a parseable logs.json
    # are partial writes from a crash mid-checkpoint — fall back past them
    # (this is what _KEEP_ROUND_DIRS > 1 retains the older snapshot for)
    payload = None
    for entry in reversed(entries):
        rd = os.path.join(rounds_dir, entry)
        try:
            with open(os.path.join(rd, "logs.json")) as f:
                payload = json.load(f)
            break
        except (FileNotFoundError, json.JSONDecodeError):
            continue
    if payload is None:
        raise FileNotFoundError(
            f"no complete round checkpoint under {rounds_dir!r} — was "
            f"the run started with checkpoint_dir set?")
    t = int(payload["round"])
    logs = [[RoundLog(**d) for d in group] for group in payload["logs"]]
    globals_ = [
        ckpt.restore(os.path.join(rd, f"global_{g}"),
                     like=net.init(jax.random.PRNGKey(0)))
        for g, net in enumerate(nets)]
    state = ckpt.load_obj(os.path.join(rd, "state"))
    return t, globals_, state, logs, payload.get("rounds_to_target")


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

class Experiment:
    """A validated, runnable experiment.

        spec = ExperimentSpec(...)            # or ExperimentSpec.load(path)
        result = Experiment(spec).run()       # RunResult

    ``run(checkpoint_dir=...)`` persists the spec + per-round state;
    ``Experiment.resume(dir)`` continues an interrupted run to
    ``spec.rounds`` with an identical trajectory.
    """

    def __init__(self, spec: ExperimentSpec):
        self.spec = spec.validate()

    def run(self, *, observers: Sequence[Observer] = (),
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 1) -> RunResult:
        return self._run(observers, checkpoint_dir, checkpoint_every,
                         resume=False)

    @classmethod
    def resume(cls, directory: str, *, observers: Sequence[Observer] = (),
               checkpoint_every: int = 1) -> RunResult:
        """Continue a checkpointed run from ``directory`` (which must
        contain the ``spec.json`` + ``rounds/`` a checkpointed
        :meth:`run` wrote)."""
        spec = ExperimentSpec.load(os.path.join(directory, "spec.json"))
        return cls(spec)._run(observers, directory, checkpoint_every,
                              resume=True)

    def _run(self, observers, checkpoint_dir, checkpoint_every, *,
             resume: bool) -> RunResult:
        spec = self.spec
        bundle = build_task_bundle(spec)
        train, val, test, parts = build_splits(spec, bundle)
        nets, client_proto = build_cohort(spec, bundle)
        source = build_source(spec, bundle, train)
        cfg = to_fl_config(spec)
        mesh = build_mesh(spec)
        heterogeneous = len(nets) > 1

        init_globals, init_state, init_logs = None, _UNSET, None
        start_round = 1
        if resume:
            (last, init_globals, init_state, init_logs,
             stored_rtt) = _load_latest_round(checkpoint_dir, nets)
            start_round = last + 1
            if stored_rtt is not None:
                # the checkpointed run already early-stopped on
                # target_accuracy — do not retrain past the stop
                results = [FLResult(logs=init_logs[g],
                                    global_params=init_globals[g])
                           for g in range(len(nets))]
                return RunResult(spec=spec, results=results,
                                 global_params=init_globals,
                                 rounds_to_target=stored_rtt,
                                 net_names=[n.name for n in nets])

        def log_fn(entry):
            g, log = entry if heterogeneous else (0, entry)
            event = RoundEvent(round=log.round, group=g,
                               n_groups=len(nets),
                               heterogeneous=heterogeneous, log=log)
            for observer in observers:
                observer(event)
            return event.stop_requested  # truthy -> driver stops the run

        round_end_hook = None
        if checkpoint_dir is not None and checkpoint_every > 0:
            os.makedirs(checkpoint_dir, exist_ok=True)
            spec.save(os.path.join(checkpoint_dir, "spec.json"))

            def round_end_hook(t, globals_, state, logs, rounds_to_target):
                if (t % checkpoint_every == 0 or t == cfg.rounds
                        or rounds_to_target is not None):
                    _save_round(checkpoint_dir, t, globals_, state, logs,
                                rounds_to_target)

        from repro.drivers import make_driver
        driver = make_driver(spec.driver.kind,
                             staleness=spec.driver.staleness,
                             prefetch=spec.driver.prefetch)

        # flight recorder: arm per spec.obs, or piggyback on a recorder
        # some caller (bench/test) armed externally.  Disarmed runs take
        # none of these branches and stay bit-identical.
        armed_here = False
        metrics_obs = None
        if spec.obs.enabled:
            _trace.arm(path=spec.obs.trace_path,
                       profile_dir=(spec.obs.profile_dir
                                    if spec.obs.profile else None))
            armed_here = True
            if spec.obs.metrics_dir:
                metrics_obs = MetricsObserver([
                    JSONLSink(os.path.join(spec.obs.metrics_dir,
                                           "metrics.jsonl")),
                    CSVSink(os.path.join(spec.obs.metrics_dir,
                                         "metrics.csv"))])
                observers = list(observers) + [metrics_obs]

        try:
            results, globals_, rounds_to_target = run_rounds(
                nets, client_proto, train, parts, val, test, cfg,
                source=source, log_fn=log_fn, heterogeneous=heterogeneous,
                mesh=mesh, client_axis=spec.sharding.client_axis,
                init_globals=init_globals, init_state=init_state,
                start_round=start_round, init_logs=init_logs,
                round_end_hook=round_end_hook, driver=driver)
            rec = _trace.recorder()
            obs_summary = rec.summary() if rec is not None else None
        finally:
            if metrics_obs is not None:
                metrics_obs.close()
            if armed_here:
                _trace.disarm()
        return RunResult(spec=spec, results=results, global_params=globals_,
                         rounds_to_target=rounds_to_target,
                         net_names=[n.name for n in nets],
                         obs=obs_summary)
