#!/usr/bin/env python3
"""Bring-up smoke run of FedDF on a TPU, through the normal entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded client axis, four chips

One chip: ``ExperimentSpec`` -> ``Experiment.run`` (``RoundEngine``, the
``sync`` driver) at the paper-scale transformer width of
``configs/feddf_paper.py`` (d_model 128, 4 layers, 4 heads, vocab 512,
128 tokens, 4 classes): 20 clients, C=0.4 (8 active), Dirichlet
alpha=0.1, FedDF fusion through the logit bank and the compiled fused
kernel.  Two rounds with a float32 bank, then one with an int8 bank.  Then
the compiled ``ensemble_kl_loss_bank`` loss and gradient are checked
against ``kernels/ref.py`` on the chip, in float32 and int8.  Weights and
data are random, made from fixed seeds.

Four chips: the same spec with the client axis sharded over
``make_client_mesh(4)``, and the heterogeneous prototype ladder (groups of
3/3/2 clients padded to the mesh), each against the same spec unsharded on
one device, in this process.

Exits nonzero, printing no result line, when JAX finds no TPU (or not
four chips for ``--four-chips``) or when any check fails.  Lines before
the last are facts about the run, not benchmark numbers; the last line is
the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: Tolerances of the on-chip kernel check: the compiled fused kernel and
#: the jnp reference both compute in float32, in different orders.
LOSS_RTOL, LOSS_ATOL = 1e-5, 1e-5
GRAD_RTOL, GRAD_ATOL = 1e-4, 1e-6
#: Largest per-round test-accuracy gap allowed between the sharded and the
#: unsharded run (12 of the 1200 test sequences).
ACC_TOL = 0.01

N_SAMPLES = 6000      # 4200 train / 600 val / 1200 test sequences
SEQ_LEN = 128
N_CLASSES = 4
DISTILL_STEPS = 300
DISTILL_BATCH = 128
POOL = 4000           # unlabeled distillation pool = logit-bank rows


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(n_chips: int):
    """The TPU devices, or exit nonzero without a result line."""
    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} TPU chip(s); JAX found "
              f"{len(devices)} {jax.default_backend()} device(s)",
              file=sys.stderr)
        sys.exit(2)
    return devices[:n_chips]


def smoke_spec(*, bank_dtype: str = "float32", rounds: int = 2,
               hetero: bool = False, shard_clients: bool = False):
    from repro.api import (CohortSpec, ExperimentSpec, FusionSpec, ModelSpec,
                           PartitionSpec, ShardingSpec, SourceSpec,
                           StrategySpec, TaskSpec, default_prototype_ladder)
    from repro.configs.feddf_paper import CONFIG as paper

    if hetero:
        prototypes = [ModelSpec.from_dict(m)
                      for m in default_prototype_ladder("tokens")]
    else:
        prototypes = [ModelSpec(name="tiny_transformer", params={
            "d_model": paper.d_model, "n_layers": paper.n_layers,
            "n_heads": paper.n_heads})]
    return ExperimentSpec(
        task=TaskSpec(name="tokens", n_samples=N_SAMPLES, params={
            "vocab": paper.vocab_size, "seq_len": SEQ_LEN,
            "n_classes": N_CLASSES}),
        partition=PartitionSpec(n_clients=20, alpha=0.1),
        cohort=CohortSpec(prototypes=prototypes),
        strategy=StrategySpec(name="feddf", fusion=FusionSpec(
            max_steps=DISTILL_STEPS, patience=DISTILL_STEPS,
            eval_every=100, batch_size=DISTILL_BATCH, logit_bank="on",
            bank_dtype=bank_dtype, use_fused_kernel="auto")),
        source=SourceSpec(name="unlabeled", params={"n": POOL}),
        sharding=ShardingSpec(shard_clients=shard_clients),
        # Adam local training (the paper's Table 6 option): SGD at the
        # spec's default lr 0.1 sends clients of this width to NaN
        rounds=rounds, client_fraction=0.4, local_epochs=2,
        local_batch_size=32, local_optimizer="adam", local_adam_lr=1e-3,
        seed=0)


class CompileWatch:
    """Backend compile time and persistent-cache traffic, from JAX's own
    monitoring events (JAX writes only programs that took at least
    ``jax_persistent_cache_min_compile_time_secs`` to compile)."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.COMPILE_EVENT:
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # JAX records this event as it writes

    def line(self) -> str:
        return (f"compiles={self.compiles} compile_s={self.compile_s:.3f} "
                f"persistent_cache_hits={self.hits} "
                f"persistent_cache_writes={self.writes}")


def run_experiment(spec, label: str):
    """``Experiment.run`` with per-round wall times.  A round ends when
    its test accuracy reaches the host, which waits for the fused
    global; the run's globals are then blocked on explicitly."""
    from repro.api import Experiment

    marks = [time.perf_counter()]

    def observer(ev):
        if ev.group == ev.n_groups - 1:
            marks.append(time.perf_counter())

    res = Experiment(spec).run(observers=[observer])
    jax.block_until_ready(res.global_params)
    for t, (a, b) in enumerate(zip(marks, marks[1:]), start=1):
        accs = [r.logs[t - 1].test_acc for r in res.results]
        print(f"{label}: round {t} wall_s={b - a:.3f} test_acc={accs}")
    return res


def check_run(res, label: str) -> None:
    for g, r in enumerate(res.results):
        check(len(r.logs) == res.spec.rounds,
              f"{label}: group {g} logged {len(r.logs)} rounds")
        for log in r.logs:
            check(log.bank == "bank",
                  f"{label}: round {log.round} group {g} bank={log.bank!r}, "
                  f"expected a built logit bank")
    for g, params in enumerate(res.global_params):
        for leaf in jax.tree.leaves(params):
            check(bool(jnp.all(jnp.isfinite(leaf))),
                  f"{label}: non-finite global parameter in group {g}")


def check_bank_kernel(b: int, n: int, c: int, temperature: float = 1.0):
    """The compiled fused-bank kernel against the jnp reference, loss and
    gradient, float32 and int8 banks."""
    from repro.core.logit_bank import quantize_rows
    from repro.kernels import ref
    from repro.kernels.ops import ensemble_kl_loss_bank

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    s = jax.random.normal(ks[0], (b, c)) * 3
    bank = jax.random.normal(ks[1], (n, c)) * 3
    idx = jax.random.randint(ks[2], (b,), 0, n)
    for dtype in ("float32", "int8"):
        if dtype == "float32":
            rows, scales, row_scale = bank, None, jnp.ones((b,))
        else:
            rows, scales = quantize_rows(bank, dtype)
            row_scale = scales[idx]
        fused = jax.jit(jax.value_and_grad(
            lambda x: ensemble_kl_loss_bank(x, rows, scales, idx,
                                            temperature)))
        plain = jax.jit(jax.value_and_grad(
            lambda x: ref.ensemble_kl_bank(x, rows, row_scale, idx,
                                           temperature)))
        check("tpu_custom_call" in fused.lower(s).compile().as_text(),
              f"bank kernel {dtype}: no compiled Pallas kernel in the "
              f"program")
        loss, grad = fused(s)
        loss_r, grad_r = plain(s)
        loss_err = abs(float(loss) - float(loss_r))
        grad_err = float(jnp.max(jnp.abs(grad - grad_r)))
        loss_ok = loss_err <= LOSS_ATOL + LOSS_RTOL * abs(float(loss_r))
        grad_ok = bool(jnp.all(jnp.abs(grad - grad_r)
                               <= GRAD_ATOL + GRAD_RTOL * jnp.abs(grad_r)))
        print(f"kernel check: ensemble_kl_loss_bank {dtype} B={b} N={n} "
              f"C={c} loss={float(loss):.8f} ref={float(loss_r):.8f} "
              f"loss_err={loss_err:.3e} (tol {LOSS_ATOL:g}+{LOSS_RTOL:g}"
              f"*|ref|) grad_max_err={grad_err:.3e} (tol {GRAD_ATOL:g}+"
              f"{GRAD_RTOL:g}*|ref|)")
        check(loss_ok and grad_ok,
              f"bank kernel {dtype} B={b} N={n} C={c} disagrees with the "
              f"reference")


def peak_memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:{st.get('peak_bytes_in_use', 'n/a')}"
                     f"/{st.get('bytes_limit', 'n/a')}")
    return "peak_bytes_in_use/bytes_limit per device " + " ".join(parts)


def one_chip(devices) -> None:
    from repro.kernels import ops

    check(ops.use_pallas("auto"), "use_fused_kernel='auto' does not select "
          "the Pallas kernels")
    check(not ops._interpret(), "Pallas kernels would run in interpret mode")

    res = run_experiment(smoke_spec(), "float32 bank")
    check_run(res, "float32 bank")
    res8 = run_experiment(smoke_spec(bank_dtype="int8", rounds=1),
                          "int8 bank")
    check_run(res8, "int8 bank")
    check(res8.results[0].logs[-1].bank_dtype == "int8",
          "int8 run did not store an int8 bank")
    check_bank_kernel(DISTILL_BATCH, POOL, N_CLASSES)


def client_devices(spec) -> list:
    """Per prototype group, the devices that hold the stacked client state
    of round 1 (None when its leaves disagree), built through the spec's
    own engine."""
    from repro.api.experiment import build_engine

    engine = build_engine(spec)
    active = engine.sample_cohort(engine.make_rng())
    groups = engine.train_clients(1, engine.init_globals(),
                                  engine.build_round_batches(1, active))
    out = []
    for g in groups:
        if g.stack is None:
            continue
        sets = {frozenset(leaf.sharding.device_set)
                for leaf in jax.tree.leaves(g.stack)}
        out.append(set().union(*sets) if len(sets) == 1 else None)
    return out


def four_chips(devices) -> None:
    from repro.launch.mesh import make_client_mesh

    want = set(make_client_mesh(4).devices.flat)
    for label, hetero in (("homogeneous", False), ("heterogeneous", True)):
        base = run_experiment(smoke_spec(hetero=hetero),
                              f"{label} unsharded")
        spec = smoke_spec(hetero=hetero, shard_clients=True)
        sharded = run_experiment(spec, f"{label} sharded")
        check_run(base, f"{label} unsharded")
        check_run(sharded, f"{label} sharded")
        gap = max(abs(a.test_acc - b.test_acc)
                  for ra, rb in zip(base.results, sharded.results)
                  for a, b in zip(ra.logs, rb.logs))
        print(f"{label}: max per-round test-acc gap sharded vs unsharded "
              f"= {gap:.6f} (tol {ACC_TOL})")
        check(gap <= ACC_TOL, f"{label}: sharded run differs from the "
              f"unsharded one by {gap} test accuracy")
        spans = client_devices(spec)
        print(f"{label}: stacked client state devices per group = "
              f"{[sorted(d.id for d in s) if s else s for s in spans]}")
        check(bool(spans) and all(s == want for s in spans),
              f"{label}: stacked client state does not span the 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-client path on four chips "
                         "and the unsharded runs it is compared with")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)

    from repro.common.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(jax.devices())} using={len(devices)} "
          f"jax={jax.__version__}")
    print(f"compile cache: {cache_dir}")
    watch = CompileWatch()
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"{watch.line()} total_wall_s={time.perf_counter() - t0:.3f}")
    print(peak_memory_line(devices))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
