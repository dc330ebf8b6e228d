#!/usr/bin/env python3
"""What the flight recorder costs when armed without the profiler.

    python3 bench/armed_cost.py --workload distilbert.feddf --seed <n> --rounds 4

One process on the cell's chips: the cell's inputs and engine as
``run.py`` builds them, round 1 as set-up, then ``--rounds`` rounds armed
and unarmed in the order armed, off, off, armed, ... (so that a drift
over the run falls on both alike), each timed from the previous round's
end to its own.  Then the recorder's own host costs: seconds per span,
per compile event (through ``jax.monitoring``, as JAX reports one) and
per disarmed span, each from a loop.  Prints one JSON line; the armed
cost of a round is its span count times the per-span cost plus its
compile events times the per-event cost."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402

LOOP = 20000


def per_call(fn, n: int = LOOP) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def host_costs() -> dict:
    import jax
    from repro.obs import trace as obs

    def one_span():
        with obs.span("x", round=1):
            pass

    def one_event():
        t = time.time()
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", t, t,
            fun_name="jit(f)")

    obs.disarm()
    null_s = per_call(one_span)
    obs.arm()
    span_s = per_call(one_span)
    event_s = per_call(one_event)
    obs.disarm()
    return {"span_s": span_s, "compile_event_s": event_s,
            "disarmed_span_s": null_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=4)
    args = ap.parse_args(argv)
    cell, config, traffic, _ = run.load_cell(args.workload)
    try:
        devices = run.check_devices(int(cell["chips"]))
    except run.NoChip as e:
        print(f"armed_cost.py: {e}", file=sys.stderr)
        return 2
    run.use_compile_cache(run.CACHE_DIR)
    from repro.drivers.sync import SyncDriver
    from repro.obs import trace as obs

    kind = run.config_kind(config)
    models = run.model_dicts(config, kind)
    fl_seed = int(args.seed) % run.SEED_SPAN
    inp = run.inputs_mod.make_inputs(args.seed, models[0], traffic,
                                     len(models))
    engine, _ = run.build_engine(config, traffic, inp, fl_seed, kind,
                                 int(cell["chips"]))
    globals0 = run.initial_globals(engine)

    plan = [(i % 4) in (0, 3) for i in range(args.rounds)]
    rows, ends = [], []
    state = {"rec": None}

    def log_fn(ev):
        p, log = ev if isinstance(ev, tuple) else (0, ev)
        if p != len(models) - 1:
            return None
        ends.append(time.perf_counter())
        i = len(ends) - 2  # the round that just ended, counted from 0
        if i >= 0:
            rec = state["rec"]
            spans = rec.spans if rec is not None else []
            rows.append({"armed": plan[i], "round_s": ends[-1] - ends[-2],
                         "spans": len(spans),
                         "compile_events": sum(
                             s["name"] in obs.COMPILE_SPANS for s in spans)})
        obs.disarm()
        state["rec"] = None
        if i + 1 >= len(plan):
            return True
        if plan[i + 1]:
            state["rec"] = obs.arm()
        return None

    try:
        SyncDriver().run(engine, log_fn=log_fn, init_globals=globals0)
    finally:
        obs.disarm()
    costs = host_costs()
    for r in rows:
        r["armed_cost_s"] = ((r["spans"] - r["compile_events"])
                             * costs["span_s"]
                             + r["compile_events"] * costs["compile_event_s"]
                             ) if r["armed"] else 0.0
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": devices[0].device_kind, "rounds": rows,
                      **costs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
