"""CI perf-regression gate over the schema'd bench history.

Every ``*_bench.py`` appends one validated record per run to
``BENCH_history.jsonl`` through :func:`benchmarks.timing.finish_bench`
(schema: ``repro.obs.history``).  This module is the single place the
acceptance thresholds live: it reads the LATEST record per
``(bench, case)`` and applies the same gates CI used to inline next to
each bench invocation — identical keys, identical thresholds, so
migrating the workflow onto this checker loosened nothing.

    PYTHONPATH=src python -m benchmarks.check_history \
        --require driver --require population

``--require`` fails the run when a bench has no record at all (without
it, only benches present in the history are gated — useful locally
where you typically ran one bench).  Exit status is non-zero on any
failure; each gate prints one PASS/SKIP/FAIL line.

Gates receive the record's **machine fingerprint** next to its metrics:
thresholds that measure thread overlap (driver speedup, buffered-async
upload throughput) are physically unreachable on a single core, so on a
``cpus < 2`` record those sub-gates report *skipped* — visibly, never
silently folded into PASS — while the correctness sub-gates of the same
record still apply.
"""
from __future__ import annotations

import argparse
import sys
from typing import Callable, Dict, List, Tuple

from repro.obs import history

# (errors, skips): errors fail CI, skips are sub-gates whose premise the
# record's machine can't meet (they print, they never pass silently)
GateResult = Tuple[List[str], List[str]]


def _one_core(machine: dict) -> bool:
    """True when the record was measured without thread-level parallelism
    (overlap speedups are unobtainable, not regressed)."""
    cpus = machine.get("cpus")
    return isinstance(cpus, int) and cpus < 2


def _distill(m: dict, machine: dict) -> GateResult:
    errs = []
    h, g = m["homogeneous"], m["heterogeneous"]
    if not h["speedup"] >= 1.5:
        errs.append(f"bank speedup regressed: {h['speedup']}")
    if not g["forward_reduction_x"] >= g["G"]:
        errs.append(f"hetero forward reduction {g['forward_reduction_x']} "
                    f"< G={g['G']}")
    return errs, []


def _distill_quant(m: dict, machine: dict) -> GateResult:
    errs = []
    if not m["bank_bytes_reduction_x"] >= 3.5:
        errs.append(f"int8 bank shrink regressed: "
                    f"{m['bank_bytes_reduction_x']}")
    if not m["teacher_agreement_drift"] <= 0.005:
        errs.append(f"int8 distill drift {m['teacher_agreement_drift']} "
                    f"> 0.5pt")
    if not m["marginal_steps_per_s_ratio"] >= 0.9:
        errs.append(f"int8 bank slowed distill: "
                    f"{m['marginal_steps_per_s_ratio']}")
    if len(m["roofline_records"]) != 4:  # fused/unfused x dtype
        errs.append(f"expected 4 roofline records, "
                    f"got {len(m['roofline_records'])}")
    return errs, []


def _driver(m: dict, machine: dict) -> GateResult:
    errs, skips = [], []
    if _one_core(machine):
        # training/fusion overlap needs a second core to run on; on one
        # core the speedup is definitionally ~1.0 and says nothing
        skips.append("overlap speedup (1-core machine)")
    elif not m["speedup"] >= 1.1:
        # local acceptance is >= 1.2x; shared-runner gate keeps slack
        errs.append(f"overlap speedup regressed: {m['speedup']}")
    if not m["async_staleness0"]["trajectory_equal"]:
        errs.append("async(staleness=0) trajectory drifted from sync")
    return errs, skips


def _population(m: dict, machine: dict) -> GateResult:
    errs, skips = [], []
    if m["buffered_degenerate"]["trajectory_equal"] is not True:
        errs.append("degenerate buffered_async drifted from sync "
                    "(must be exact)")
    if _one_core(machine):
        skips.append("buffered upload throughput (1-core machine)")
    elif not m["uploads_ratio"] >= 1.3:
        errs.append(f"buffered upload throughput regressed: "
                    f"{m['uploads_ratio']}")
    if not m["final_acc_drift"] <= 0.005:
        errs.append(f"buffered drift {m['final_acc_drift']} > 0.5pt")
    return errs, skips


def _robustness(m: dict, machine: dict) -> GateResult:
    errs = []
    if not abs(m["screened"]["drift"]) <= 0.01:
        errs.append(f"screened drift {m['screened']['drift']} > 1pt")
    if not (m["screened"]["finite"] and m["trimmed_mean"]["finite"]):
        errs.append("non-finite globals under faults")
    if not m["screened"]["quarantined"] > 0:
        errs.append("quarantine telemetry empty under chaos")
    # armed-but-idle fault seam costs <= 5% wall time (local
    # acceptance; CI slack for shared-runner noise)
    if not m["idle_overhead_frac"] <= 0.15:
        errs.append(f"idle fault-seam overhead {m['idle_overhead_frac']}")
    return errs, []


def _obs(m: dict, machine: dict) -> GateResult:
    errs = []
    if not m["overhead_frac"] <= 0.02:
        errs.append(f"armed flight-recorder overhead "
                    f"{m['overhead_frac']} > 2%")
    if m["trajectory_equal"] is not True:
        errs.append("armed trajectory drifted from disarmed "
                    "(must be bit-identical)")
    return errs, []


def _dist(m: dict, machine: dict) -> GateResult:
    """Distributed-runtime acceptance (benchmarks/dist_bench.py;
    docs/distributed.md)."""
    errs = []
    if m["degenerate"]["trajectory_equal"] is not True:
        errs.append("degenerate distributed drifted from sync "
                    "(must be bit-identical)")
    if not abs(m["chaos"]["drift"]) <= 0.01:
        errs.append(f"defended chaos drift {m['chaos']['drift']} > 1pt")
    if not (m["chaos"]["wire_retries"] > 0
            or m["chaos"]["deadline_misses"] > 0):
        errs.append("chaos telemetry empty (no retries/deadline misses "
                    "recorded — did the faults fire?)")
    if not m["chaos"]["min_pods_alive"] < m["chaos"]["n_pods"]:
        errs.append("chaos pod kill not observed by liveness tracking")
    if not m["undefended"]["degraded"]:
        errs.append("undefended run did not degrade (the defense gates "
                    "are not being exercised)")
    if not m["wire"]["int8_reduction_x"] >= 3.0:
        errs.append(f"int8 bytes-on-wire reduction "
                    f"{m['wire']['int8_reduction_x']} < 3x vs fp32")
    if m["restart"]["trajectory_equal"] is not True:
        errs.append("restarted fusion pod drifted from uninterrupted run")
    if not m["restart"]["replayed"] > 0:
        errs.append("restart replayed nothing from the wire log")
    return errs, []


def _paper(m: dict, machine: dict) -> GateResult:
    """Paper-table records (benchmarks/common.emit): presence + sanity —
    accuracy thresholds stay with each table's own acceptance docs.
    The timing slot may carry a derived scalar (some benches emit a
    drift there), so the gate only requires a finite non-negative
    number."""
    errs = []
    w = m.get("wall_s")
    if not (isinstance(w, (int, float)) and w >= 0 and w == w):
        errs.append(f"invalid wall_s: {w!r}")
    if not m.get("name"):
        errs.append("record has no table name")
    return errs, []


GATES: Dict[str, Callable[[dict, dict], GateResult]] = {
    "distill": _distill,
    "distill_quant": _distill_quant,
    "driver": _driver,
    "population": _population,
    "robustness": _robustness,
    "obs": _obs,
    "dist": _dist,
    "paper": _paper,
}


def check(path=None, require=()) -> List[str]:
    """Gate the latest record per (bench, case); returns failure strings."""
    latest = history.latest(path)
    by_bench = {}
    for (bench, case), rec in latest.items():
        by_bench.setdefault(bench, {})[case] = rec
    failures = []
    for bench in require:
        if bench not in by_bench:
            failures.append(f"{bench}: required but no history record")
    for bench in sorted(by_bench):
        gate = GATES.get(bench)
        if gate is None:
            print(f"SKIP {bench}: no gate registered")
            continue
        for case, rec in sorted(by_bench[bench].items()):
            try:
                errs, skips = gate(rec["metrics"],
                                   rec.get("machine") or {})
            except (KeyError, TypeError) as e:
                errs, skips = [f"malformed metrics: {e!r}"], []
            for e in errs:
                failures.append(f"{bench}[{case}]: {e}")
            status = "FAIL" if errs else ("SKIP" if skips else "PASS")
            print(f"{status} {bench}[{case}]"
                  + "".join(f"\n  - {e}" for e in errs)
                  + "".join(f"\n  ~ skipped: {s}" for s in skips))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--history", default=None,
                    help="history path (default: $BENCH_HISTORY_OUT or "
                         "BENCH_history.jsonl)")
    ap.add_argument("--require", action="append", default=[],
                    metavar="BENCH",
                    help="fail unless this bench has a record "
                         "(repeatable)")
    args = ap.parse_args(argv)
    failures = check(args.history, args.require)
    if failures:
        print(f"{len(failures)} gate failure(s):", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("all gates passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
