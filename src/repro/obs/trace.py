"""Flight-recorder span tracing: zero-dependency, disarmed-by-default.

``with span("train_clients", round=t):`` wraps every host-level phase of
the round engine plus the driver seams (async dispatch/join,
buffered-async fill/fuse waves, fault-pipeline screening, logit-bank
build/reuse, checkpoint write).  Spans are HOST spans — they never sit
inside a jit trace, so arming them cannot change what XLA compiles and
the disarmed path is a single module-global ``is None`` check returning
a shared no-op context manager (bit-identity with the seed trajectory
is pinned in tests, overhead is gated in ``benchmarks/obs_bench.py``).

Each finished span is one JSONL line::

    {"name": "train_clients", "t0": 3.21, "t1": 4.05, "dur_s": 0.84,
     "depth": 1, "parent": "round", "thread": "MainThread",
     "round": 7, "driver": "buffered_async", "wave": 12}

Timestamps are ``time.perf_counter()`` (monotonic) offsets from the
recorder's arm time, so idle gaps between spans on different threads —
the async overlap the drivers exist to create — are directly
subtractable.  Nesting (``depth``/``parent``) is tracked per-thread;
driver attribution rides in via :func:`set_context`, which pushes
ambient key/values (``driver=...``) that stamp every span opened on any
thread until popped.

Optional jax-profiler passthrough: when armed with ``profile_dir`` the
recorder calls ``jax.profiler.start_trace`` and enters a
``TraceAnnotation(name)`` alongside each span, so the same span
taxonomy shows up on XLA timelines.  jax is imported lazily.  A profiler
that was asked for and cannot start raises: a profiled run that wrote no
trace would read as one that had nothing to trace.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional


class _NullSpan:
    """Shared no-op context manager returned while disarmed."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **attrs):
        pass


_NULL = _NullSpan()

#: module-global recorder slot; ``None`` == disarmed (the common case).
_RECORDER: Optional["FlightRecorder"] = None


class _Span:
    __slots__ = ("rec", "name", "attrs", "t0", "_ann")

    def __init__(self, rec: "FlightRecorder", name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs
        self._ann = None

    def annotate(self, **attrs) -> None:
        """Attach attributes discovered mid-span (fault stats etc.)."""
        self.attrs.update(attrs)

    def __enter__(self):
        self.rec._push(self.name)
        if self.rec._profiling:
            self._ann = self.rec._annotate(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:  # pragma: no cover - profiler teardown quirk
                pass
        self.rec._pop(self.name, self.t0, t1, self.attrs)
        return False


class FlightRecorder:
    """Collects finished spans in memory and (optionally) appends them
    to a JSONL file as they close.  One recorder is armed at a time via
    :func:`arm`; :func:`span` routes through it."""

    def __init__(self, path: Optional[str] = None,
                 profile_dir: Optional[str] = None):
        self.path = path
        self.profile_dir = profile_dir
        self.spans: List[dict] = []
        self._epoch = time.perf_counter()
        self._local = threading.local()
        self._lock = threading.Lock()
        self._context: Dict[str, object] = {}
        self._f = None
        self._profiling = False
        if path:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a")

    # -- per-thread nesting stack -------------------------------------
    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, name: str) -> None:
        self._stack().append(name)

    def _pop(self, name: str, t0: float, t1: float, attrs: dict) -> None:
        st = self._stack()
        parent = st[-2] if len(st) > 1 else None
        depth = len(st) - 1
        st.pop()
        rec = {"name": name,
               "t0": t0 - self._epoch, "t1": t1 - self._epoch,
               "dur_s": t1 - t0, "depth": depth, "parent": parent,
               "thread": threading.current_thread().name}
        with self._lock:
            rec.update(self._context)
            rec.update(attrs)
            self.spans.append(rec)
            if self._f is not None:
                self._f.write(json.dumps(rec) + "\n")
                self._f.flush()

    # -- ambient attribution ------------------------------------------
    def set_context(self, **attrs) -> None:
        """Stamp ``attrs`` onto every subsequently closed span (any
        thread) until overwritten; ``key=None`` removes a key."""
        with self._lock:
            for k, v in attrs.items():
                if v is None:
                    self._context.pop(k, None)
                else:
                    self._context[k] = v

    # -- jax profiler passthrough -------------------------------------
    def _start_profiler(self) -> None:
        if not self.profile_dir:
            return
        import jax
        os.makedirs(self.profile_dir, exist_ok=True)
        jax.profiler.start_trace(self.profile_dir)
        self._profiling = True

    def _stop_profiler(self) -> None:
        if not self._profiling:
            return
        try:  # pragma: no cover - exercised only with a profiler backend
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._profiling = False

    def _annotate(self, name: str):
        try:  # pragma: no cover - profiler-armed path
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            return ann
        except Exception:
            return None

    # -- summaries -----------------------------------------------------
    def phase_totals(self) -> Dict[str, float]:
        """Total seconds per span name."""
        out: Dict[str, float] = {}
        with self._lock:
            for s in self.spans:
                out[s["name"]] = out.get(s["name"], 0.0) + s["dur_s"]
        return out

    def per_round(self) -> Dict[int, Dict[str, float]]:
        """``{round: {span name: total seconds}}`` for round-stamped
        spans.  Buffered-async training runs in numbered *waves* inside
        a round's ``fill`` span; those wave spans carry ``wave=`` (not
        ``round=``) and aggregate under :meth:`phase_totals` instead."""
        out: Dict[int, Dict[str, float]] = {}
        with self._lock:
            for s in self.spans:
                r = s.get("round")
                if r is None:
                    continue
                row = out.setdefault(int(r), {})
                row[s["name"]] = row.get(s["name"], 0.0) + s["dur_s"]
        return out

    def summary(self) -> dict:
        """The ``RunResult.summary()["obs"]`` payload: phase totals,
        per-round phase breakdown, and the async idle gap (total time a
        driver spent blocked joining a fusion future)."""
        totals = self.phase_totals()
        per_round = self.per_round()
        idle = totals.get("join_fusion", 0.0) + totals.get("join_batches",
                                                           0.0)
        return {"n_spans": len(self.spans),
                "phase_totals_s": totals,
                "idle_gap_s": idle,
                "per_round": {str(k): v
                              for k, v in sorted(per_round.items())}}

    def close(self) -> None:
        self._stop_profiler()
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


def arm(path: Optional[str] = None, profile_dir: Optional[str] = None
        ) -> FlightRecorder:
    """Install (and return) a recorder; replaces any armed one.  Raises,
    leaving none armed, when ``profile_dir`` is set and the jax profiler
    cannot start."""
    global _RECORDER
    disarm()
    rec = FlightRecorder(path=path, profile_dir=profile_dir)
    try:
        rec._start_profiler()
    except BaseException:
        rec.close()
        raise
    _RECORDER = rec
    return rec


def disarm() -> None:
    global _RECORDER
    if _RECORDER is not None:
        _RECORDER.close()
    _RECORDER = None


def recorder() -> Optional[FlightRecorder]:
    return _RECORDER


def span(name: str, **attrs):
    """Context manager timing ``name``; free no-op while disarmed."""
    rec = _RECORDER
    if rec is None:
        return _NULL
    return _Span(rec, name, attrs)


def set_context(**attrs) -> None:
    """Ambient span attribution (no-op while disarmed)."""
    rec = _RECORDER
    if rec is not None:
        rec.set_context(**attrs)


def load_spans(path: str) -> List[dict]:
    """Parse a span JSONL file back into dicts (validation + tests)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
