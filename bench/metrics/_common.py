"""Shared by the per-layer readers: the data file beside a reader, and
the window's spans."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def data(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def span_ms_per_round(ctx, span: str):
    """Host milliseconds per window round of the flight recorder's
    ``span`` (None when it never closed in the window)."""
    durs = [s["dur_s"] for s in ctx["spans"] if s["name"] == span]
    return 1e3 * sum(durs) / ctx["rounds"] if durs else None


def module_ms_per_round(ctx, name: str):
    """Device milliseconds per window round of the programs listed in
    ``<name>.json`` (None when none of them ran)."""
    import tracefile
    s = tracefile.module_s(ctx["trace"], data(name)["modules"])
    return None if s is None else 1e3 * s / ctx["rounds"]
