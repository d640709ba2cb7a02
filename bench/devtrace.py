"""From a profiler trace to the numbers the per-layer metrics read.

``load`` takes the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps two
lists: the device operations (TPU planes, line ``XLA Ops``) and the host
spans that the harness opened (``bench.*`` TraceAnnotations).  ``reduce``
works on those lists alone, so it can be checked on a small recorded
trace:

* the traced window is the host span ``bench.traced``;
* busy time is the union of the device operations' intervals inside it,
  averaged over the devices;
* the top device operations are the op labels (instruction names without
  their number) that took the most summed device time, leaving out the
  control flow (``while``) whose event spans its body's operations;
* each idle gap, a stretch of the window in which no operation ran, is
  charged to the innermost ``bench.*`` span around its midpoint, or to
  ``host.other``.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"
#: ops whose trace event spans the ops of their body (a scan's loop)
CONTAINERS = frozenset({"while", "conditional", "call"})

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def find_xplane(trace_dir) -> Optional[str]:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None


def load(path) -> dict:
    """{"devices": {plane: [(label, start, end)]}, "host": [(span, start,
    end)]} from an .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    evs.append((op_label(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"devices": devices, "host": host}


def op_label(name: str) -> str:
    """An operation's HLO instruction name without its number: the event
    name is the instruction's text, ``%dequant_matmul_packed_pallas.40 =
    f32[128,16384] custom-call(...)``, and the label is
    ``dequant_matmul_packed_pallas``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(evs: Sequence[Event], lo: float, hi: float):
    for name, s, e in evs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def _innermost(spans: Sequence[Event], t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host.other"


def reduce(events: dict, top: int = 10) -> Optional[dict]:
    """busy_s, window_s, device_ops and idle_gaps of the traced window, and
    the summed device seconds of every op label (``op_s``)."""
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW_SPAN]
    devices = {k: v for k, v in events["devices"].items() if v}
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    spans = [ev for ev in events["host"] if ev[0] != WINDOW_SPAN]
    op_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for evs in devices.values():
        clipped = list(_clip(evs, lo, hi))
        for name, s, e in clipped:
            if name not in CONTAINERS:
                op_s[name] += (e - s) * 1e-9
        merged = union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_innermost(spans, (a + b) / 2)] += (b - a) * 1e-9
    n = len(devices)
    op_s = {k: v / n for k, v in op_s.items()}
    return {
        "busy_s": busy / n,
        "window_s": (hi - lo) * 1e-9,
        "op_s": op_s,
        "device_ops": sorted(([k, v] for k, v in op_s.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def seconds_matching(op_s: Dict[str, float], pattern: str) -> float:
    """Summed device seconds of the ops whose label matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in op_s.items() if rx.search(k))
