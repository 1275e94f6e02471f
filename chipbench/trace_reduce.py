"""Reduce a profiler trace to the numbers the benchmark reports.

Input: the events of one ``.xplane.pb`` as ``(plane, line, name,
start_ns, duration_ns)`` tuples (:func:`load_events`), so the reduction
itself runs on plain data and can be checked on a small recorded trace.

* The traced window is the host span ``chipbench.window``.
* A device is a plane named ``/device:TPU:<n>``.  Its busy time is the
  union of the intervals of its operations (the ``XLA Ops`` line where
  the plane has one, else every line of the plane), clipped to the
  window; idle is the rest of the window.
* An operation is named ``<program>/<op>``: the XLA module it ran in
  (``XLA Modules`` line) and the HLO instruction's name.
* Host spans are the ``chipbench.<name>`` events of the host planes.
  An idle gap is labelled by the innermost span that covers its
  midpoint (``harness`` where only the window does).  The profiler puts
  host and device events on one clock only to a millisecond or two (a
  recorded v5e trace shows device programs about 1.5 ms before the host
  span that issued them), so device time is not split by host span.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

WINDOW = "chipbench.window"
PREFIX = "chipbench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_label(name: str, module: str | None) -> str:
    """``<program>/<op>``: the HLO instruction's name without its text,
    inside the XLA module (jitted program, hash dropped) it ran in."""
    op = name.split(" = ", 1)[0]
    return f"{module.split('(', 1)[0]}/{op}" if module else op


def load_events(path: str) -> list[tuple]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if device or name.startswith(PREFIX):
                    out.append((plane.name, line.name, name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def reduce_events(events: list[tuple], top: int = 10) -> dict:
    spans: dict[str, list[tuple[float, float]]] = defaultdict(list)
    device_lines: dict[str, set] = defaultdict(set)
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PREFIX):
            device_lines[plane].add(line)
        elif name.startswith(PREFIX):
            spans[name[len(PREFIX):]].append((start, start + dur))
    windows = spans.pop("window", [])
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    if not device_lines:
        raise ValueError("no device plane in the trace")
    w0, w1 = windows[0]
    modules: dict[str, list] = defaultdict(list)
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PREFIX) and line == MODULES_LINE:
            modules[plane].append((start, start + dur, name))
    for iv in modules.values():
        iv.sort()
    busy_by_dev: dict[str, list] = defaultdict(list)
    op_time: dict[str, float] = defaultdict(float)
    for plane, line, name, start, dur in events:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        if OPS_LINE in device_lines[plane] and line != OPS_LINE:
            continue
        lo, hi = max(start, w0), min(start + dur, w1)
        if hi <= lo:
            continue
        busy_by_dev[plane].append((lo, hi))
        mods = modules.get(plane, [])
        i = bisect.bisect_right(mods, (start, float("inf"), "")) - 1
        module = mods[i][2] if i >= 0 and mods[i][1] >= start else None
        op_time[op_label(name, module)] += hi - lo
    busy = {p: union(iv) for p, iv in busy_by_dev.items()}
    n_dev = len(device_lines)
    busy_ns = sum(sum(b - a for a, b in iv) for iv in busy.values()) / n_dev
    span_iv = {k: union(clip(v, w0, w1)) for k, v in spans.items()}
    # Idle gaps of the first device, labelled by the host span around them.
    first = busy.get(sorted(device_lines)[0], [])
    gaps, cursor = [], w0
    for a, b in first + [(w1, w1)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    flat = sorted((b - a, k, a, b) for k, iv in span_iv.items()
                  for a, b in iv)
    labelled = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        label = next((k for _, k, lo, hi in flat if lo <= mid <= hi),
                     "harness")
        labelled.append((label, (b - a) / 1e9))
    ops = sorted(op_time.items(), key=lambda x: -x[1])[:top]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": n_dev,
        "span_s": {k: sum(b - a for a, b in iv) / 1e9
                   for k, iv in span_iv.items()},
        "device_ops": [[n, t / 1e9] for n, t in ops],
        "idle_gaps": [[k, s] for k, s in labelled],
    }
