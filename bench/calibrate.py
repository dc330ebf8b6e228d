#!/usr/bin/env python3
"""Readings that the limits in ``limits.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \\
        [--control-seeds 1 2 3] [--faults half_batch bank_row accuracy]

For each seed: round 1 of the program (the cell's own sizes, the same
entry and programs as a benchmark run, stopped after round 1) against
the float32 reference, which gives the lower readings.  For each control
seed: the reference in bfloat16, put in the program's place, against the
float32 reference, which gives the upper readings.  Each fault is planted
in the program's round 1 on the control seeds:

    half_batch      every local step sees the first half of its batch only
    bank_row        the first logit-bank row comes out negated
    accuracy        every accuracy the round reports comes out 0.1 too high
    kl_half_batch   the distillation loss (the fused kernel, or the plain
                    path off the chip) takes the first half of each batch
    kl_temperature  the distillation loss runs at twice the temperature
    kl_scale        the distillation loss comes out doubled
    exchange        on several chips, the exchange between them left out:
                    the mean and the bank take the first chip's clients

A step that leaves its state unchanged (``unchanged`` for the clients,
``distill_unchanged`` for a distillation chunk) reads 1 on the change it
leaves out by that number's definition and needs no run on the chip.
Prints one JSON line per reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402  (sets up the program's import path)


#: the program's module attributes that :func:`plant` may rebind
PATCHED = (("repro.core.feddf", "resolve_bank"),
           ("repro.core.feddf", "avg_logits_kl_pre"),
           ("repro.core.feddf", "_get_chunk"),
           ("repro.kernels.ops", "ensemble_kl_loss_bank"))


def patched():
    """(module, name, value) of each attribute :func:`plant` may rebind,
    as it stands, to put back after a planted run."""
    import importlib
    return [(importlib.import_module(m), n,
             getattr(importlib.import_module(m), n)) for m, n in PATCHED]


def _plant_kl(fault: str):
    """The distillation loss, on both of the program's paths, broken."""
    from repro.core import feddf as feddf_mod
    from repro.kernels import ops
    bank_kl, pre_kl = ops.ensemble_kl_loss_bank, feddf_mod.avg_logits_kl_pre
    half = lambda a: a[:a.shape[0] // 2]
    if fault == "kl_half_batch":
        ops.ensemble_kl_loss_bank = lambda s, rows, scales, idx, **kw: \
            bank_kl(half(s), rows, scales, half(idx), **kw)
        feddf_mod.avg_logits_kl_pre = lambda s, t, temp: \
            pre_kl(half(s), half(t), temp)
    elif fault == "kl_temperature":
        ops.ensemble_kl_loss_bank = lambda s, rows, scales, idx, \
            temperature=1.0: bank_kl(s, rows, scales, idx,
                                     temperature=2.0 * temperature)
        feddf_mod.avg_logits_kl_pre = lambda s, t, temp: \
            pre_kl(s, t, 2.0 * temp)
    else:
        ops.ensemble_kl_loss_bank = lambda *a, **kw: 2.0 * bank_kl(*a, **kw)
        feddf_mod.avg_logits_kl_pre = lambda *a: 2.0 * pre_kl(*a)


def plant(engine, fault: str):
    """Break round 1 of ``engine`` underneath, as ``fault`` says (the
    program's module attributes it rebinds are listed in ``PATCHED``)."""
    import jax.numpy as jnp
    from repro.core import feddf as feddf_mod

    if fault == "half_batch":
        updates = engine.updates
        wrap = lambda f: (lambda params, xb, yb, *rest: f(
            params, xb[:, :, :xb.shape[2] // 2], yb[:, :, :yb.shape[2] // 2],
            *rest))
        engine._updates = [wrap(f) for f in updates]
    elif fault == "bank_row":
        resolve = feddf_mod.resolve_bank

        def resolve_bank(*a, **kw):
            bank, reason = resolve(*a, **kw)
            if bank is not None:
                bank.logits = bank.logits.at[0].set(-bank.logits[0])
            return bank, reason
        feddf_mod.resolve_bank = resolve_bank
    elif fault == "accuracy":
        evaluate, aggregate = engine.evaluate_round, engine.aggregate

        def aggregate_(*a, **kw):
            globals_, state, infos, dropped, ens = aggregate(*a, **kw)
            for info in infos:
                if info.get("pre_distill_acc") is not None:
                    info["pre_distill_acc"] += 0.1
            return (globals_, state, infos, dropped,
                    None if ens is None else ens + 0.1)

        def evaluate_round(*a, **kw):
            logs = evaluate(*a, **kw)
            for log in logs:
                log.test_acc += 0.1
            return logs
        engine.aggregate = aggregate_
        engine.evaluate_round = evaluate_round
    elif fault == "unchanged":
        engine._updates = [
            (lambda params, xb, *rest: __import__("jax").tree.map(
                lambda a: jnp.broadcast_to(a, (xb.shape[0],) + a.shape),
                params)) for _ in engine.nets]
    elif fault == "exchange":
        if engine.mesh is None:
            raise ValueError("the exchange fault needs a cell on several "
                             "chips")
        import jax
        aggregate = engine.aggregate

        def aggregate_(t, groups, state):
            for g in groups:
                if g.stack is not None:
                    leaf = jax.tree.leaves(g.stack)[0]
                    per = leaf.sharding.shard_shape(leaf.shape)[0]
                    g.stack = jax.tree.map(lambda a: a[:per], g.stack)
                    g.weights = g.weights[:per]
            return aggregate(t, groups, state)
        engine.aggregate = aggregate_
    elif fault in ("kl_half_batch", "kl_temperature", "kl_scale"):
        _plant_kl(fault)
    elif fault == "distill_unchanged":
        get_chunk = feddf_mod._get_chunk

        def get_chunk_(student_net, fns, source, fusion, *a, **kw):
            _, extra = get_chunk(student_net, fns, source, fusion, *a, **kw)
            return (lambda params, opt_state, key, step0, *rest: (
                params, opt_state, key, step0 + fusion.eval_every)), extra
        feddf_mod._get_chunk = get_chunk_
    else:
        raise ValueError(f"unknown fault {fault!r}")


def program_round_one(config, traffic, seed: int, kind, fault=None,
                      chips: int = 1):
    """(inputs, proto, the program's round-1 outputs) for ``seed``."""
    import jax
    from repro.core import logit_bank
    from repro.drivers.sync import SyncDriver

    models = run.model_dicts(config, kind)
    fl_seed = int(seed) % run.SEED_SPAN
    inp = run.inputs_mod.make_inputs(seed, models[0], traffic, len(models))
    engine, proto = run.build_engine(config, traffic, inp, fl_seed, kind,
                                     chips)
    saved = patched()
    if fault is not None:
        plant(engine, fault)
    tap = run.RoundOneTap(engine)
    got = {}

    def round_one(logs):
        got["logs"] = logs
        tap.close()

    win = run.Window(None, len(models), round_one, None, None)
    g0 = run.initial_globals(engine)
    try:
        results, _, _ = SyncDriver().run(engine, log_fn=win,
                                         init_globals=g0)
        jax.block_until_ready([r.global_params for r in results])
    finally:
        tap.close()
        for mod, name, value in saved:
            setattr(mod, name, value)
    prog = run.program_round_one(tap, got["logs"])
    del results, engine, g0, tap
    logit_bank.PERSISTENT_BANK.clear()
    gc.collect()
    return inp, proto, fl_seed, prog


def reference(models, traffic, inp, proto, fl_seed, dtype, kind):
    import jax.numpy as jnp
    import reference as ref_mod
    ms = [ref_mod.Model(m, traffic, kind, dtype=getattr(jnp, dtype))
          for m in models]
    return ref_mod.round_one(ms, traffic, inp, proto, fl_seed,
                             len(models) > 1)


def dump(path: str, one, ref) -> None:
    """The per-leaf change norms of round 1 and a summary of its bank
    against the reference's, for reading the numbers again offline."""
    import numpy as np
    d = {k: getattr(one, k) for k in ("clients", "fused", "chunks",
                                      "distilled",
                                      "test_acc", "val_acc",
                                      "pre_acc", "ens_acc", "first_grad",
                                      "val_history", "seconds")}
    if one.bank is not None and ref.bank is not None:
        diff = np.abs(one.bank.astype(np.float64) - ref.bank)
        d["bank"] = {"max_abs_gap": float(diff.max()),
                     "rms_ref": float(np.sqrt(np.mean(ref.bank ** 2))),
                     "gap_quantiles": [float(q) for q in np.quantile(
                         diff, [0.5, 0.9, 0.99, 0.999])]}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(d, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--chips", action="store_true",
                    help="fail without the cell's TPU chips")
    ap.add_argument("--dump", default=None,
                    help="directory for each reading's per-leaf norms")
    args = ap.parse_args(argv)
    import compare
    cell, config, traffic, _ = run.load_cell(args.workload)
    kind = run.config_kind(config)
    chips = int(cell["chips"])
    if args.chips:
        run.check_devices(chips)
        run.use_compile_cache(run.CACHE_DIR)
    models = run.model_dicts(config, kind)
    emit = lambda d: print(json.dumps(d), flush=True)
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        inp, proto, fl_seed, prog = program_round_one(
            config, traffic, seed, kind, chips=chips)
        ref = reference(models, traffic, inp, proto, fl_seed, "float32",
                        kind)
        if args.dump:
            dump(os.path.join(args.dump, f"{seed}-reference.json"), ref, ref)
            dump(os.path.join(args.dump, f"{seed}-program.json"), prog, ref)
        emit({"seed": seed, "kind": "program",
              "numbers": compare.numbers(prog, ref),
              "not_compared": compare.not_compared(prog, ref),
              "seconds": time.perf_counter() - t0,
              "reference_seconds": ref.seconds,
              "reference_live_bytes_max": ref.live_bytes_max,
              "val_history": ref.val_history,
              "worst": compare.worst_leaves(prog, ref)})
        if seed not in args.control_seeds:
            continue
        t0 = time.perf_counter()
        ctl = reference(models, traffic, inp, proto, fl_seed, "bfloat16",
                        kind)
        if args.dump:
            dump(os.path.join(args.dump, f"{seed}-control.json"), ctl, ref)
        emit({"seed": seed, "kind": "control:bfloat16",
              "numbers": compare.numbers(ctl, ref),
              "not_compared": compare.not_compared(ctl, ref),
              "seconds": time.perf_counter() - t0,
              "worst": compare.worst_leaves(ctl, ref)})
        for fault in args.faults:
            t0 = time.perf_counter()
            _, _, _, bad = program_round_one(config, traffic, seed, kind,
                                             fault, chips)
            if args.dump:
                dump(os.path.join(args.dump, f"{seed}-{fault}.json"), bad,
                     ref)
            emit({"seed": seed, "kind": f"fault:{fault}",
                  "numbers": compare.numbers(bad, ref),
                  "not_compared": compare.not_compared(bad, ref),
                  "seconds": time.perf_counter() - t0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
