"""Vectorized federated round engine (see docs/round_engine.md).

One engine serves both Algorithm 1 (homogeneous) and Algorithm 3
(heterogeneous prototypes).  A round decomposes into four explicit,
individually-resumable phases that round *drivers* (``repro.drivers``)
compose:

  ``sample_cohort``   draw the round's active client set (the ONLY phase
                      that advances the host rng, so completed rounds can
                      be replayed draw-for-draw on resume);
  ``train_clients``   train every prototype group's clients in ONE jitted
                      program (``client.make_batched_local_update``), one
                      client after another, each for its own step count —
                      batches stacked to [K_g, n_steps, B, ...], FedProx /
                      quantize / DP inside the jit, optionally the client
                      axis sharded over a device mesh;
  ``aggregate``       optional drop-worst filter + dispatch of the stacks
                      to the configured :class:`ServerStrategy`
                      (``core/strategies.py`` registry) -> new globals;
  ``evaluate_round``  test/val accuracy per prototype -> ``RoundLog``.

Batch building (``build_round_batches``) is split out of ``train_clients``
because it is a pure host-side function of ``(round, cohort)`` — the
async-pipelined driver prefetches it rounds ahead without touching the
trajectory.  Batch tensors are padded to a run-fixed step capacity and
client count, with a prefix step mask; the device runs only each client's
masked-in steps, so each trajectory matches the sequential reference path
and the compile count stays bounded for the whole run instead of one
program per client per distinct shape.

The run-fixed per-prototype client capacities, padded up to mesh
divisibility, are what let HETEROGENEOUS cohorts shard their client axis
over a device mesh (``attach_mesh`` / the ``multihost`` driver; see
docs/bucketing.md).

:func:`run_rounds` keeps the historic flat API: it builds a
:class:`RoundEngine` and hands it to a driver from the registry
(``repro.drivers``; the default ``sync`` driver IS the historic loop,
extracted — trajectories are pinned bit-identical in
``tests/test_drivers.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import feddf as feddf_mod
from repro.core.client import (REAL_STEPS, build_batched_batches, evaluate,
                               make_batched_local_update, n_local_steps)
from repro.common.pytree import tree_isfinite, tree_take
from repro.core.dropworst import drop_worst_stacked
from repro.core.nets import Net
from repro.core.strategies import GroupRound, RoundContext, get_strategy
from repro.data.distill_sources import DistillSource
from repro.data.synthetic import Dataset
from repro.obs import trace as _trace
from repro.optim.optimizers import Optimizer, sgd
from repro.dist.config import DistConfig
from repro.population.config import FaultConfig, PopulationConfig


def _spanned(name: str):
    """Wrap a phase method in a flight-recorder span.  Free while
    disarmed (one module-global ``is None`` check); armed, the span is
    stamped with the driver's step index as ``round=`` — the actual
    round for sync/async drivers, the WAVE number when buffered_async
    trains inside a fill wave (see docs/observability.md)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, t, *args, **kwargs):
            if _trace.recorder() is None:
                return fn(self, t, *args, **kwargs)
            with _trace.span(name, round=int(t)):
                return fn(self, t, *args, **kwargs)
        return wrapped
    return deco

# distinguishes "no init_state passed" from a legitimately-None state
# (most strategies keep no server state at all)
_UNSET = object()


@dataclasses.dataclass
class FLConfig:
    rounds: int = 20
    client_fraction: float = 0.4  # C
    local_epochs: int = 20        # E
    local_batch_size: int = 32
    local_lr: float = 0.1
    strategy: str = "fedavg"      # any name in the strategy registry
    prox_mu: float = 0.01
    server_momentum: float = 0.3  # beta for fedavgm
    drop_worst: bool = False
    seed: int = 0
    local_optimizer: str = "sgd"  # sgd | adam (Table 6 ablation)
    local_adam_lr: float = 1e-3   # adam local lr (sgd uses local_lr)
    quantize: Optional[Callable] = None
    fusion: feddf_mod.FusionConfig = dataclasses.field(
        default_factory=feddf_mod.FusionConfig)
    feddf_init_from: str = "average"  # Table 5 ablation: average | previous
    target_accuracy: Optional[float] = None  # stop early when reached
    # client-level DP on uploads (paper §3 privacy extension; core/privacy.py)
    dp_clip: Optional[float] = None
    dp_noise_multiplier: float = 0.0
    # population / traffic / sampler axis (docs/population.md); the
    # defaults reproduce the classic fixed-roster uniform draw bit-for-bit
    population: PopulationConfig = dataclasses.field(
        default_factory=PopulationConfig)
    # fault injection + defenses (docs/robustness.md); all-zero rates
    # disable every fault path, keeping historic trajectories bit-identical
    faults: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    # per-side trim fraction for the trimmed_mean strategy
    trim_frac: float = 0.2
    # distributed fusion-pod / client-pod runtime (docs/distributed.md);
    # only the "distributed" driver reads this
    dist: DistConfig = dataclasses.field(default_factory=DistConfig)


@dataclasses.dataclass
class RoundLog:
    round: int
    test_acc: float
    val_acc: float
    ensemble_acc: Optional[float] = None
    pre_distill_acc: Optional[float] = None
    distill_steps: int = 0
    n_participants: int = 0
    n_dropped: int = 0
    # teacher batch-forwards this round's fusion cost (0 when the shared
    # logit bank served a group, or for non-distillation strategies)
    teacher_forwards: int = 0
    # how the fusion sourced its teacher logits this round: "bank" (built),
    # "bank_reused" (persistent bank hit), "on_the_fly", or
    # "skipped_small_run" (the auto heuristic predicted too few distill
    # steps to amortize a bank build); "" for non-distillation strategies
    bank: str = ""
    # the bank's storage dtype ("float32" | "bfloat16" | "int8" |
    # "fp8_e4m3") and device bytes (quantized rows + per-row scales) —
    # the observable memory the quantized dtypes shrink; ""/0 when no
    # bank served this round
    bank_dtype: str = ""
    bank_nbytes: int = 0
    # population telemetry (buffered_async driver; docs/population.md).
    # Defaults keep pre-population checkpoints loadable via RoundLog(**d).
    staleness_hist: Optional[List[int]] = None  # uploads fused at age s
    buffer_fill: int = 0          # ready-but-unconsumed uploads after agg
    n_straggling: int = 0         # in-flight uploads not yet arrived
    n_dropped_uploads: int = 0    # uploads lost to dropout since last agg
    n_stale_dropped: int = 0      # uploads discarded as > max_staleness
    eff_participants: float = 0.0  # sum of (1+s)^-a importance weights
    # fault telemetry (docs/robustness.md).  Defaults keep pre-fault
    # checkpoints loadable via RoundLog(**d).
    n_corrupted: int = 0          # uploads a fault fired on this round
    n_quarantined: int = 0        # uploads rejected by screening
    n_retries: int = 0            # re-dispatch attempts after rejection
    n_teachers_filtered: int = 0  # teachers dropped by consensus filter
    fused: bool = True            # False when quorum skipped aggregation
    rolled_back: bool = False     # non-finite globals restored to last-good
    # distributed wire telemetry (docs/distributed.md).  Defaults keep
    # pre-dist checkpoints loadable via RoundLog(**d).
    wire_bytes_up: int = 0        # accepted UPLOAD frame bytes this round
    wire_bytes_down: int = 0      # TRAIN frame bytes dispatched this round
    n_wire_retries: int = 0       # TRAIN re-dispatches (deadline/CRC)
    n_crc_failures: int = 0       # frames rejected by checksum
    n_deadline_misses: int = 0    # uploads past their per-attempt deadline
    n_wire_lost: int = 0          # clients lost at the wire layer
    n_pods_alive: int = 0         # live client pods at round end


@dataclasses.dataclass
class FLResult:
    logs: List[RoundLog]
    global_params: dict
    rounds_to_target: Optional[int] = None

    @property
    def final_acc(self) -> float:
        return self.logs[-1].test_acc if self.logs else 0.0

    @property
    def best_acc(self) -> float:
        return max(l.test_acc for l in self.logs) if self.logs else 0.0


def _make_opt(cfg: FLConfig) -> Optimizer:
    if cfg.local_optimizer == "adam":
        from repro.optim.optimizers import adam
        return adam(cfg.local_adam_lr)
    return sgd(cfg.local_lr)


@dataclasses.dataclass
class RoundBatches:
    """One prototype group's host-built round inputs (pure function of
    ``(round, cohort)`` — prefetchable), padded to the run-fixed client
    capacity and scan length."""

    ks: List[int]                # active client ids of this group
    xb: np.ndarray               # [cap_clients, steps_cap, B, ...]
    yb: np.ndarray               # [cap_clients, steps_cap, B]
    step_mask: np.ndarray        # [cap_clients, steps_cap], prefix masks
    dp_keys: np.ndarray          # [cap_clients, 2]
    k_real: int                  # un-padded client count
    weights: np.ndarray          # [k_real] local dataset sizes, in ks order
    # accounting (docs/bucketing.md):
    real_steps: int              # unmasked client-steps this group runs
    padded_slots: int            # cap_clients * steps_cap: batch slots
                                 # built on the host (only real_steps run)

    @property
    def cap_clients(self) -> int:
        return int(self.xb.shape[0])


class RoundEngine:
    """The per-round phases plus the precomputed run-wide state (compiled
    client updates, fixed scan lengths, device-resident eval sets).

    Drivers own the loop: which rounds run, in what order client training
    overlaps fusion, and when checkpoints fire.  The engine owns the math:
    every phase is a deterministic function of its inputs, so any driver
    that feeds the same inputs produces the same trajectory.
    """

    def __init__(
        self,
        nets: List[Net],
        client_proto: Sequence[int],
        train: Dataset,
        parts: Sequence[np.ndarray],
        val: Dataset,
        test: Dataset,
        cfg: FLConfig,
        *,
        source: Optional[DistillSource] = None,
        heterogeneous: bool = False,
        mesh=None,
        client_axis: str = "data",
    ):
        self.nets = nets
        self.client_proto = list(client_proto)
        self.train = train
        self.parts = parts
        self.val = val
        self.test = test
        self.cfg = cfg
        self.source = source
        self.heterogeneous = heterogeneous
        self.mesh = mesh
        self.client_axis = client_axis

        self.strategy = get_strategy(cfg.strategy)
        self.n_clients = len(parts)
        self.n_active = max(1, int(round(cfg.client_fraction
                                         * self.n_clients)))
        self.n_proto = len(nets)
        # fixed scan lengths AND fixed client-axis sizes per prototype ->
        # one compile per prototype for the whole run (group sizes vary
        # round to round in the heterogeneous case; padded clients get an
        # all-False step mask, run no step and are sliced off afterwards).
        # All of this is a pure function of the STATIC per-client dataset
        # sizes, so it never changes across rounds.
        self.client_steps = [
            n_local_steps(len(parts[k]), cfg.local_batch_size,
                          cfg.local_epochs)
            for k in range(self.n_clients)]
        self.steps_cap = [
            max([self.client_steps[k] for k in range(self.n_clients)
                 if self.client_proto[k] == p] or [1])
            for p in range(self.n_proto)]
        proto_counts = [sum(1 for q in self.client_proto if q == p)
                        for p in range(self.n_proto)]
        self.k_cap = [min(self.n_active, c) if c else 1
                      for c in proto_counts]
        self.batch_seed_mult = 99991 if heterogeneous else 100_003
        # population / scheduler seam (docs/population.md): cohort draws
        # go through a pluggable sampler bound to run-fixed population
        # facts.  The default (uniform sampler, population == partitions)
        # reproduces the historic rng.choice draw bit-for-bit.
        from repro.population.scheduler import SamplerContext, make_sampler
        cfg.population.validate()
        self.population_size = int(cfg.population.size or self.n_clients)
        pop_part = np.arange(self.population_size,
                             dtype=np.int64) % self.n_clients
        self.sampler = make_sampler(cfg.population.sampler).bind(
            SamplerContext(
                n_clients=self.population_size,
                n_partitions=self.n_clients,
                proto=np.asarray(self.client_proto, np.int64)[pop_part],
                # one cell per prototype, filled to its meshless client
                # capacity (the capacity_aware sampler's guide)
                bucket=np.zeros(self.population_size, np.int64),
                bucket_client_caps=[[k] for k in self.k_cap]))
        self._population = None  # built lazily by population()
        cfg.faults.validate()
        self._fault_model = None  # built lazily by fault_model()
        # transfer the eval sets to device ONCE per run: `evaluate`,
        # drop-worst and the distillation val loop otherwise re-upload the
        # same numpy arrays every round (labels stay host-side, they are
        # compared there)
        self.val_x = jnp.asarray(val.x)
        self.test_x = jnp.asarray(test.x)
        # compiled per-prototype batched updates, built lazily so a driver
        # can still attach a mesh (attach_mesh) before first training
        self._updates: Optional[List[Callable]] = None
        if self.mesh is not None:  # ShardingSpec/--shard-clients path
            self._validate_mesh(self.mesh, self.client_axis)

    def _validate_mesh(self, mesh, client_axis: str) -> None:
        """Fail loudly where BOTH mesh paths (constructor-supplied and
        driver-attached) converge, instead of deep inside shard_map.

        Heterogeneous runs pad every prototype's client capacity up to
        mesh divisibility instead (the padded slots carry all-False step
        masks, run no step and are sliced off), so only the homogeneous
        path keeps the strict check."""
        if self.heterogeneous:
            return
        axis = mesh.shape[client_axis]
        bad = [k for k in self.k_cap if k % axis]
        if bad:
            raise ValueError(
                f"active cohort size(s) {bad} do not divide the "
                f"{client_axis!r} mesh axis ({axis} devices); pick "
                f"client_fraction/n_clients so K is a multiple of the "
                f"device count")

    def _client_cap(self, p: int) -> int:
        """Run-fixed client-axis size of prototype p: no round can
        activate more of its clients than ``k_cap``, so this never
        retraces; with a mesh it is rounded up to axis divisibility
        (except on the strictly-validated homogeneous path)."""
        cap = self.k_cap[p]
        if self.mesh is not None and self.heterogeneous:
            axis = self.mesh.shape[self.client_axis]
            cap = -(-cap // axis) * axis
        return cap

    # -- driver-facing setup ----------------------------------------------

    def attach_mesh(self, mesh, client_axis: str = "data") -> None:
        """Shard the client axis of local training over ``mesh`` (multihost
        driver seam).  Must run before the first ``train_clients`` call.
        Heterogeneous engines pad their run-fixed per-prototype client
        capacities up to mesh divisibility, so they shard too."""
        if self._updates is not None:
            raise RuntimeError("attach_mesh must be called before the "
                               "first train_clients call")
        self._validate_mesh(mesh, client_axis)
        self.mesh = mesh
        self.client_axis = client_axis

    @property
    def updates(self) -> List[Callable]:
        if self._updates is None:
            prox = self.strategy.local_prox_mu(self.cfg)
            self._updates = [
                make_batched_local_update(
                    self.nets[p], _make_opt(self.cfg), prox_mu=prox,
                    quantize=self.cfg.quantize, dp_clip=self.cfg.dp_clip,
                    dp_noise_multiplier=self.cfg.dp_noise_multiplier,
                    mesh=self.mesh, client_axis=self.client_axis,
                    # the engine rebuilds the batch tensors every round, so
                    # their device buffers are donatable scratch
                    donate_batches=True)
                for p in range(self.n_proto)]
        return self._updates

    def make_rng(self) -> np.random.Generator:
        return np.random.default_rng(self.cfg.seed)

    def init_globals(self) -> List[dict]:
        return [self.nets[p].init(jax.random.PRNGKey(
            self.cfg.seed + p if self.heterogeneous else self.cfg.seed))
            for p in range(self.n_proto)]

    def init_state(self, globals_: List[dict]):
        return self.strategy.init_state(globals_)

    # -- phases -----------------------------------------------------------

    def sample_cohort(self, rng: np.random.Generator) -> np.ndarray:
        """Draw the round's active clients.  The single rng consumer:
        replaying t-1 calls reproduces round t's draw exactly (resume).

        The draw is delegated to the configured cohort sampler
        (population/scheduler.py); the default uniform sampler over a
        population the size of the partition roster IS the historic
        ``rng.choice(n_clients, n_active, replace=False)`` call.  With a
        larger registered population, sampled ids map onto data
        partitions round-robin (several devices share a shard)."""
        with _trace.span("sample_cohort"):
            active = self.sampler.sample(rng, self.n_active)
            if self.population_size != self.n_clients:
                active = np.asarray(active) % self.n_clients
            return active

    def population(self):
        """The lazily-built :class:`PopulationManager` (buffered-async
        driver seam): registry + traffic model + upload buffer sharing
        this engine's bound sampler."""
        if self._population is None:
            from repro.population.manager import PopulationManager
            self._population = PopulationManager(
                self.cfg.population, seed=self.cfg.seed,
                n_partitions=self.n_clients,
                partition_sizes=[len(p) for p in self.parts],
                client_steps=self.client_steps,
                client_proto=self.client_proto,
                client_bucket=np.zeros(self.n_clients, np.int64),
                n_active=self.n_active, sampler=self.sampler,
                faults=self.cfg.faults)
        return self._population

    def fault_model(self):
        """The lazily-built counter-based :class:`FaultModel` (None when
        no fault class is enabled — the historic zero-overhead path)."""
        if self._fault_model is None and self.cfg.faults.enabled:
            from repro.population.faults import FaultModel
            self._fault_model = FaultModel(
                self.cfg.faults, self.cfg.seed, self.population_size)
        return self._fault_model

    def fault_pipeline(self, t: int, groups: List[GroupRound],
                       batches: List[Optional[RoundBatches]]):
        """Spanned wrapper around :meth:`_fault_pipeline_body`; the span
        carries the screen/retry/quarantine outcome as attributes and
        the same counts feed the ``core.faults.*`` registry counters."""
        with _trace.span("fault_pipeline", round=int(t)) as sp:
            stats = self._fault_pipeline_body(t, groups, batches)
            if stats is not None:
                sp.annotate(corrupted=stats["corrupted"],
                            quarantined=stats["quarantined"],
                            retries=stats["retries"])
                from repro.obs.metrics import REGISTRY
                REGISTRY.counter("core.faults.corrupted").add(
                    stats["corrupted"])
                REGISTRY.counter("core.faults.quarantined").add(
                    stats["quarantined"])
                REGISTRY.counter("core.faults.retries").add(
                    stats["retries"])
            return stats

    def _fault_pipeline_body(self, t: int, groups: List[GroupRound],
                             batches: List[Optional[RoundBatches]]):
        """Inject, screen and retry on the trained group stacks — the sync
        driver's fault seam (docs/robustness.md).

        Corruption is keyed on ``(seed, wave=t, client, attempt)`` so the
        fault trace never replays across resumes; a retry redraws the
        *transport* faults on the client's clean params (training is
        deterministic), while byzantine clients stay corrupted on every
        attempt and end up quarantined.  Screening (finite-ness + robust-z
        of the delta norm within the cohort) mutates the groups in place,
        dropping quarantined rows.  Returns a stats dict, or None when
        faults are disabled (the stacks are then untouched — bit-identity).
        """
        faults = self.cfg.faults
        fm = self.fault_model()
        if fm is None:
            return None
        from repro.population.faults import (delta_norm, leaves_finite,
                                             outlier_mask, robust_z)
        stats = {"corrupted": 0, "quarantined": 0, "retries": 0,
                 "dispatched": 0, "kept": 0}
        for p, (g, rb) in enumerate(zip(groups, batches)):
            if g.stack is None or rb is None:
                continue
            # the sync/async drivers hand RoundBatches; the distributed
            # driver hands the plain per-proto client-id lists its wire
            # collection assembled (frames carry ids, not batch plans)
            ids = rb.ks if hasattr(rb, "ks") else list(rb)
            flat, treedef = jax.tree.flatten(g.stack)
            host = [np.asarray(l) for l in flat]
            base = [np.asarray(l) for l in jax.tree.leaves(g.prev_global)]
            k = len(ids)
            stats["dispatched"] += k
            clean = [[h[i] for h in host] for i in range(k)]
            rows, touched = [], False
            for i, c in enumerate(ids):
                row, kinds = fm.corrupt(t, c, clean[i], base, attempt=0)
                rows.append(row)
                if kinds:
                    stats["corrupted"] += 1
                    touched = True
            keep = np.ones(k, np.bool_)
            if faults.screen_active:
                # Pass 1 — transport retries: resolve non-finite uploads
                # BEFORE the norm screen, otherwise a burst of NaN drops
                # can gut the cohort and hand the finite median to a
                # byzantine minority (the screen would then bless the
                # attackers and reject the honest survivors).
                next_attempt = np.ones(k, np.int64)
                for i in range(k):
                    while (not leaves_finite(rows[i])
                           and next_attempt[i] <= faults.retries):
                        stats["retries"] += 1
                        row, _ = fm.corrupt(t, ids[i], clean[i], base,
                                            attempt=int(next_attempt[i]))
                        next_attempt[i] += 1
                        if leaves_finite(row):
                            rows[i] = row
                # Pass 2 — adversarial screen over the finite cohort.
                norms = np.array([
                    delta_norm(r, base) if leaves_finite(r) else np.nan
                    for r in rows])
                bad = outlier_mask(norms, faults.norm_sigma)
                ok_norms = norms[~bad]
                med = (float(np.median(ok_norms)) if ok_norms.size else 0.0)
                mad = (float(np.median(np.abs(ok_norms - med)))
                       if ok_norms.size else 0.0)
                for i in np.flatnonzero(bad):
                    accepted = False
                    for attempt in range(int(next_attempt[i]),
                                         faults.retries + 1):
                        stats["retries"] += 1
                        row, _ = fm.corrupt(t, ids[i], clean[i], base,
                                            attempt=attempt)
                        if not leaves_finite(row):
                            continue
                        nrm = delta_norm(row, base)
                        if (ok_norms.size and float(robust_z(
                                np.asarray([nrm]), med, mad)[0])
                                > faults.norm_sigma):
                            continue
                        rows[i] = row
                        accepted = True
                        break
                    if not accepted:
                        keep[i] = False
                        stats["quarantined"] += 1
                        self.sampler.penalize([int(ids[i])], 0.5)
                touched = touched or not keep.all()
            stats["kept"] += int(keep.sum())
            if not touched:
                continue
            kept_i = np.flatnonzero(keep)
            new_host = [
                np.stack([rows[i][li] for i in kept_i], axis=0)
                if kept_i.size else np.zeros((0,) + h.shape[1:], h.dtype)
                for li, h in enumerate(host)]
            if kept_i.size:
                g.stack = jax.tree.unflatten(
                    treedef, [jnp.asarray(h) for h in new_host])
            else:
                g.stack = None
            g.weights = np.asarray(g.weights)[kept_i]
            if g.importance is not None:
                g.importance = np.asarray(g.importance)[kept_i]
        return stats

    def quorum_met(self, stats) -> bool:
        """Did enough uploads survive screening to fuse this round?"""
        import math
        q = self.cfg.faults.quorum
        if q is None or stats is None or stats["dispatched"] == 0:
            return True
        return stats["kept"] >= math.ceil(q * stats["dispatched"] - 1e-9)

    def guard_globals(self, globals_: List[dict], last_good: List[dict]
                      ) -> Tuple[List[dict], List[bool]]:
        """Divergence rollback: any group whose fused globals contain a
        non-finite value is restored to its last-good params.  Gated on
        faults being enabled so historic runs never pay the device
        reduction; returns ``(globals, rolled_back per group)``."""
        rolled = [False] * len(globals_)
        if not self.cfg.faults.enabled:
            return globals_, rolled
        out = []
        for p, (gp, lg) in enumerate(zip(globals_, last_good)):
            if bool(tree_isfinite(gp)):
                out.append(gp)
            else:
                out.append(lg)
                rolled[p] = True
        return out, rolled

    @_spanned("build_round_batches")
    def build_round_batches(
            self, t: int, active: np.ndarray
    ) -> List[Optional[RoundBatches]]:
        """Host-side batch tensors per prototype group — a pure function
        of ``(t, active)``: no rng state, no globals, safe to prefetch."""
        cfg = self.cfg
        by_proto: List[List[int]] = [[] for _ in range(self.n_proto)]
        for k in active:
            by_proto[self.client_proto[k]].append(int(k))
        out: List[Optional[RoundBatches]] = []
        for p in range(self.n_proto):
            ks = by_proto[p]
            if not ks:
                out.append(None)
                continue
            seeds = [cfg.seed * self.batch_seed_mult + t * 131 + k
                     for k in ks]
            xb, yb, step_mask = build_batched_batches(
                self.train.x, self.train.y, [self.parts[k] for k in ks],
                cfg.local_batch_size, cfg.local_epochs, seeds,
                n_steps=self.steps_cap[p])
            if cfg.dp_clip is not None:
                dp_keys = np.stack([
                    np.asarray(jax.random.PRNGKey(
                        cfg.seed * 7919 + t * 131 + k)) for k in ks])
            else:
                dp_keys = np.zeros((len(ks), 2), np.uint32)
            cap_k = self._client_cap(p)
            if len(ks) < cap_k:  # pad the client axis to fixed size
                pad = cap_k - len(ks)
                zpad = lambda a: np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                xb, yb, step_mask, dp_keys = (
                    zpad(xb), zpad(yb), zpad(step_mask), zpad(dp_keys))
            weights = np.array([float(len(self.parts[k])) for k in ks])
            out.append(RoundBatches(
                ks=ks, xb=xb, yb=yb, step_mask=step_mask, dp_keys=dp_keys,
                k_real=len(ks), weights=weights,
                real_steps=int(step_mask.sum()),
                padded_slots=cap_k * self.steps_cap[p]))
        return out

    def train_clients(self, t: int, globals_: List[dict],
                      batches: List[Optional[RoundBatches]]
                      ) -> List[GroupRound]:
        """Run every group's batched local update from ``globals_``.  The
        async driver may pass globals one fusion STALER than sync would
        (bounded staleness; see docs/drivers.md).  The span and the
        ``core.client.real_steps`` counter carry the client-steps the
        round's cohort holds."""
        with _trace.span("train_clients", round=int(t)) as sp:
            groups: List[GroupRound] = []
            real_steps = 0
            for p, rb in enumerate(batches):
                if rb is None:
                    groups.append(GroupRound(self.nets[p], globals_[p],
                                             None, np.zeros(0)))
                    continue
                stack = self.updates[p](globals_[p], jnp.asarray(rb.xb),
                                        jnp.asarray(rb.yb), globals_[p],
                                        jnp.asarray(rb.step_mask),
                                        jnp.asarray(rb.dp_keys))
                if rb.k_real < rb.cap_clients:
                    stack = tree_take(stack, np.arange(rb.k_real))
                real_steps += rb.real_steps
                groups.append(GroupRound(self.nets[p], globals_[p], stack,
                                         rb.weights))
            sp.annotate(real_steps=real_steps)
            REAL_STEPS.add(real_steps)
        return groups

    @_spanned("aggregate")
    def aggregate(self, t: int, groups: List[GroupRound], state
                  ) -> Tuple[List[dict], object, List[dict], List[int],
                             Optional[float]]:
        """Drop-worst filter + strategy dispatch.  Returns
        ``(new_globals, new_state, per-group infos, n_dropped per group,
        ensemble_acc)``."""
        cfg = self.cfg
        dropped = [0] * self.n_proto
        if cfg.drop_worst:
            for p, g in enumerate(groups):
                if g.stack is None:
                    continue
                kept, kept_w, kept_i = drop_worst_stacked(
                    g.net, g.stack, g.weights, self.val_x, self.val.y,
                    self.train.n_classes)
                dropped[p] = len(g.weights) - len(kept_i)
                g.stack, g.weights = kept, np.asarray(kept_w)
                if g.importance is not None:
                    g.importance = np.asarray(g.importance)[kept_i]

        ens_acc = None
        if self.heterogeneous:
            from repro.core.ensemble import ensemble_accuracy_stacked
            ens_acc = ensemble_accuracy_stacked(
                [(g.net, g.stack) for g in groups if g.stack is not None],
                self.test_x, self.test.y)

        ctx = RoundContext(cfg=cfg, round=t,
                           heterogeneous=self.heterogeneous,
                           source=self.source, val_x=self.val_x,
                           val_y=self.val.y, test_x=self.test_x,
                           test_y=self.test.y)
        globals_, state, infos = self.strategy.aggregate(groups, state, ctx)
        return globals_, state, infos, dropped, ens_acc

    @_spanned("evaluate_round")
    def evaluate_round(self, t: int, globals_: List[dict],
                       groups: List[GroupRound], infos: List[dict],
                       dropped: List[int], ens_acc: Optional[float]
                       ) -> List[RoundLog]:
        cfg = self.cfg
        out = []
        for p in range(self.n_proto):
            acc = evaluate(self.nets[p], globals_[p], self.test_x,
                           self.test.y, quantize=cfg.quantize)
            vacc = evaluate(self.nets[p], globals_[p], self.val_x,
                            self.val.y, quantize=cfg.quantize)
            out.append(RoundLog(
                round=t, test_acc=acc, val_acc=vacc, ensemble_acc=ens_acc,
                pre_distill_acc=infos[p].get("pre_distill_acc"),
                distill_steps=infos[p].get("distill_steps", 0),
                n_participants=len(groups[p].weights),
                n_dropped=dropped[p],
                teacher_forwards=infos[p].get("teacher_forwards", 0),
                bank=infos[p].get("bank", ""),
                bank_dtype=infos[p].get("bank_dtype", ""),
                bank_nbytes=infos[p].get("bank_nbytes", 0),
                n_teachers_filtered=infos[p].get("teachers_filtered", 0),
                rolled_back=bool(infos[p].get("diverged", False))))
        return out

    def target_reached(self, round_logs: List[RoundLog]) -> bool:
        """Rounds-to-target early-stop criterion.  Homogeneous: the global
        model's test accuracy.  Heterogeneous: the best prototype's test
        accuracy this round (every client owns one of the prototypes, so
        the fleet has reached the target when its best group has)."""
        if self.cfg.target_accuracy is None:
            return False
        return max(l.test_acc for l in round_logs) >= self.cfg.target_accuracy


def run_rounds(
    nets: List[Net],
    client_proto: Sequence[int],          # client k -> prototype index
    train: Dataset,
    parts: Sequence[np.ndarray],
    val: Dataset,
    test: Dataset,
    cfg: FLConfig,
    *,
    source: Optional[DistillSource] = None,
    log_fn: Optional[Callable] = None,
    heterogeneous: bool = False,
    mesh=None,
    client_axis: str = "data",
    init_globals: Optional[List[dict]] = None,
    init_state=_UNSET,
    start_round: int = 1,
    init_logs: Optional[List[List["RoundLog"]]] = None,
    round_end_hook: Optional[Callable] = None,
    driver=None,
) -> Tuple[List[FLResult], List[dict], Optional[int]]:
    """The shared round loop.  Returns (per-prototype results, final
    globals, rounds_to_target).  ``mesh`` shards the client axis of local
    training over ``client_axis``; heterogeneous runs pad their run-fixed
    per-prototype client capacities up to mesh divisibility, the
    homogeneous path requires the active cohort size to
    divide the axis size (validated loudly).  Homogeneous
    callers pass one net and ``client_proto`` all zeros; ``log_fn``
    receives ``RoundLog`` (homogeneous) or ``(group, RoundLog)``
    (heterogeneous) to match the historic APIs, and may return a truthy
    value to request a stop after the current round (the
    ``RoundEvent.request_stop`` seam).

    ``driver`` selects the round driver (``repro.drivers`` registry): a
    name, a :class:`repro.drivers.Driver` instance, or None for the
    default ``sync`` driver — the historic serial loop, bit-identical.

    Resume support (``repro.api.Experiment.resume``): pass the
    checkpointed ``init_globals`` / ``init_state`` / ``init_logs`` and
    ``start_round = <last completed round> + 1``; the cohort-sampling rng
    replays the completed rounds' draws so the trajectory is identical to
    an uninterrupted run.  ``round_end_hook(t, globals_, state, logs,
    rounds_to_target)`` fires after every completed round in round order
    (this is the checkpoint seam) for every driver."""
    from repro.drivers import resolve_driver

    engine = RoundEngine(nets, client_proto, train, parts, val, test, cfg,
                         source=source, heterogeneous=heterogeneous,
                         mesh=mesh, client_axis=client_axis)
    drv = resolve_driver(driver)
    return drv.run(engine, log_fn=log_fn, init_globals=init_globals,
                   init_state=init_state, start_round=start_round,
                   init_logs=init_logs, round_end_hook=round_end_hook)
