"""Reduction of a profiler trace of the window to numbers.

A trace is read into plain rows, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}``, so the
reduction runs the same on a trace that a test keeps as JSON. Device
planes are ``/device:TPU:<n>``; their operations are the events of the
line named ``XLA Ops``. The traced window is the host span
``bench/traced`` that the harness opens around the calls it traces;
the harness's other ``bench/`` spans name what the host was doing in
each idle gap.

- busy: the union of the device-op intervals inside the window, per
  device, averaged over the devices that ran anything;
- idle share: 1 minus busy over the window;
- top ops: device self time (an op's time less the ops nested in it)
  by operation name, the HLO name without its number (clipped to the
  window);
- idle gaps: the longest stretches of the window with no device
  operation, each named for the innermost harness span around its
  middle.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench/traced"
SPAN_PREFIX = "bench/"


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def rows_from_file(path: str, keep=None) -> dict:
    """The trace as plain rows. ``keep(plane, line)`` may drop lines."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            if keep is not None and not keep(plane.name, line.name):
                continue
            lines.append({"name": line.name, "events": [
                [e.name, e.start_ns, e.duration_ns] for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _wanted(plane: str, line: str) -> bool:
    return plane.startswith(DEVICE_PREFIX) and line == OPS_LINE or (
        not plane.startswith(DEVICE_PREFIX))


def reduce_file(path: str) -> dict:
    return reduce_rows(rows_from_file(path, _wanted))


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_rows(rows: dict, top: int = 10) -> dict | None:
    """busy_s, window_s, idle share, top device ops and longest idle
    gaps of the window; None when the trace holds no window span."""
    spans = []
    for plane in rows["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        return None
    _, lo, hi = max(windows, key=lambda s: s[2] - s[1])
    window_ns = hi - lo
    per_device = []
    by_op: dict = {}
    merged_all = []
    for plane in rows["planes"]:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            clipped = []
            for name, start, dur in line["events"]:
                s, e = max(start, lo), min(start + dur, hi)
                if e > s:
                    clipped.append((s, e, op_name(name)))
            intervals += [(s, e) for s, e, _ in clipped]
            for name, ns in _self_times(clipped):
                by_op[name] = by_op.get(name, 0) + ns
        if intervals:
            merged = _union(intervals)
            per_device.append(sum(e - s for s, e in merged))
            merged_all.append(merged)
    busy_ns = sum(per_device) / len(per_device) if per_device else 0.0
    gaps = []
    if merged_all:
        merged = merged_all[0]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(spans, (s + e) / 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
        "devices": len(per_device),
        "top_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[name, s] for name, s in gaps[:top]],
    }


_OP = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)*(?:\s*=|$)")


def op_name(hlo: str) -> str:
    """``%_miller_t.1 = s32[...] custom-call(...)`` -> ``_miller_t``."""
    m = _OP.match(hlo)
    return m.group(1) if m else hlo.split(" ", 1)[0][:64]


def _self_times(events):
    """(name, self ns) of intervals where some nest inside others."""
    out = []
    stack = []  # [name, start, end, child ns]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        top = stack.pop()
        out.append((top[0], top[2] - top[1] - top[3]))
    return out


def _span_at(spans, t) -> str:
    """The innermost harness span that holds time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside bench spans"


def idle_share_pct(t) -> float | None:
    if not t or t.get("idle_share") is None or not t.get("busy_s"):
        return None
    return 100.0 * t["idle_share"]
