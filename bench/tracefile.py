"""From a profiler trace to the numbers the per-layer metrics read.

``load_dir`` reads the ``.xplane.pb`` a run wrote into a plain dict that
the reductions below take, and that a test can hold as JSON:

    {"devices": {"0": {"modules": [[name, t0_ns, t1_ns], ...],
                       "ops": [[module, op, t0_ns, t1_ns], ...]}},
     "host": [[name, t0_ns, t1_ns], ...]}

``modules`` are the XLA programs the device ran (the ``XLA Modules`` line
of a ``/device:TPU:n`` plane: ``jit_<function>(<fingerprint>)``), ``ops``
the operations inside them (``XLA Ops``), each tagged with the module it
ran in, and ``host`` the annotated host spans (``TraceAnnotation``, which
the program's flight recorder emits per phase) of the Python thread.
All times share the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def module_name(event_name: str) -> str:
    """``jit_counted(829189...)`` -> ``jit_counted``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def from_profile(pd) -> dict:
    """The plain dict of a ``jax.profiler.ProfileData``."""
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {line.name: list(_events(line)) for line in plane.lines}
            mods = sorted(([module_name(n), a, b] for n, a, b in
                           lines.get("XLA Modules", [])),
                          key=lambda e: e[1])
            ops = []
            starts = [e[1] for e in mods]
            for n, a, b in sorted(lines.get("XLA Ops", []),
                                  key=lambda e: e[1]):
                i = bisect.bisect_right(starts, a) - 1
                mod = mods[i][0] if i >= 0 and a <= mods[i][2] else ""
                ops.append([mod, n, a, b])
            devices[m.group(1)] = {"modules": mods, "ops": ops}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend([n, a, b] for n, a, b in _events(line))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def load_dir(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, as the plain dict."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(paths[-1]))


# -- reductions ---------------------------------------------------------------

def union(intervals: Iterable[Sequence[float]]) -> List[Tuple[float, float]]:
    """Merged, sorted [t0, t1) intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((float(a), float(b)) for a, b in intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def busy_s(tr: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = tr["devices"].values()
    if not devs:
        return 0.0
    return sum(_length(union((o[2], o[3]) for o in d["ops"]))
               for d in devs) / len(devs) / 1e9


def module_s(tr: dict, names: Sequence[str]) -> Optional[float]:
    """Device seconds of the modules named ``names``, averaged over the
    devices; None when none of them ran."""
    want = set(names)
    tot, seen = 0.0, False
    for d in tr["devices"].values():
        for n, a, b in d["modules"]:
            if n in want:
                tot += b - a
                seen = True
    return tot / len(tr["devices"]) / 1e9 if seen else None


def ops_s(tr: dict, module: Optional[str], contains: str,
          device: Optional[str] = None) -> Tuple[Optional[float], int]:
    """(device seconds, count) of the operations whose text contains
    ``contains``, inside ``module`` (any module when None); seconds are
    summed over the devices (or on ``device`` alone).  (None, 0) when
    there is none."""
    tot, n = 0.0, 0
    for dev, d in tr["devices"].items():
        if device is not None and dev != device:
            continue
        for mod, name, a, b in d["ops"]:
            if (module is None or mod == module) and contains in name:
                tot += b - a
                n += 1
    return (tot / 1e9 if n else None), n


def idle_gaps(tr: dict, device: str = "0", top: int = 10) -> List[list]:
    """The ``top`` longest gaps between operations on ``device``, each
    named by the innermost host span open at its middle."""
    d = tr["devices"].get(device)
    if d is None:
        return []
    busy = union((o[2], o[3]) for o in d["ops"])
    gaps = [(b1, a2) for (_, b1), (a2, _) in zip(busy, busy[1:])]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        open_ = [h for h in tr["host"] if h[1] <= mid <= h[2]]
        name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "none"
        out.append([name, (b - a) / 1e9])
    return out


def top_ops(tr: dict, device: str = "0", top: int = 10) -> List[list]:
    """The ``top`` operations of ``device`` by total time, named
    ``<module>/<instruction>``."""
    d = tr["devices"].get(device)
    if d is None:
        return []
    tot: Dict[str, float] = {}
    for mod, name, a, b in d["ops"]:
        key = f"{mod}/{op_name(name)}"
        tot[key] = tot.get(key, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return [[k, v / 1e9] for k, v in best]


def breakdown(tr: dict) -> dict:
    return {"device_ops": top_ops(tr), "idle_gaps": idle_gaps(tr)}
