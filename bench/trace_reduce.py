"""Reduce a JAX profiler trace to the device's busy time and a breakdown.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData``.  What the reduction takes from it:

* device operations: the events of the line ``XLA Ops`` in each device
  plane (``/device:TPU:<n>``; its other lines are ``XLA Modules``, whole
  programs, and ``Async XLA Ops``, copies that overlap them);
* the traced window: from the first to the last end of the benchmark's own
  host spans (``bench.ingest``, ``bench.materialize``, written by
  ``jax.profiler.TraceAnnotation``), on the host plane ``/host:CPU``, in
  the line of the thread that ran them (``python3`` on a TPU v5e host).

``busy_s`` is the union of the device operations' intervals inside the
window, averaged over the devices; ``window_s`` the window's length.  The
breakdown lists the operations with the most device time (summed by the
name XLA gives them) and the longest idle gaps of the first device, each
named by the benchmark span and the innermost other host event on the same
thread at the gap's middle: what the host was doing while the device
waited.
"""
from __future__ import annotations

import glob
import os

HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _innermost(events, t):
    """Name of the shortest event in ``events`` (start, end, name) that
    holds time ``t``, or None."""
    best = None
    for a, b, name in events:
        if a <= t < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else None


def op_name(event_name: str) -> str:
    """The instruction's name from a TPU ``XLA Ops`` event, whose name is
    the whole HLO line (``%fusion.12 = s32[...] fusion(...), ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes) -> dict | None:
    """``{"busy_s", "window_s", "device_ops", "idle_gaps"}`` from planes
    shaped as ``ProfileData``'s (``name``, ``lines`` with ``name`` and
    ``events`` with ``name``, ``start_ns``, ``duration_ns``); None when the
    trace holds no device operation or no benchmark span."""
    spans, host = [], []
    devices = []
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                mine = [e for e in evs if e[2].startswith(SPAN_PREFIX)]
                if mine:
                    spans += mine
                    host += [e for e in evs
                             if not e[2].startswith(SPAN_PREFIX)]
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(ops)
    if not spans or not devices:
        return None
    lo = min(a for a, _, _ in spans)
    hi = max(b for _, b, _ in spans)
    busy = []
    per_op = {}
    for ops in devices:
        inside = _clip([(a, b) for a, b, _ in ops], lo, hi)
        busy.append(sum(b - a for a, b in _union(inside)))
        for a, b, name in ops:
            if b > lo and a < hi:
                per_op[name] = per_op.get(name, 0) + min(b, hi) - max(a, lo)
    merged = _union(_clip([(a, b) for a, b, _ in devices[0]], lo, hi))
    gaps, t = [], lo
    for a, b in merged + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:TOP]:
        mid = (a + b) / 2
        where = _innermost(spans, mid) or "between passes"
        doing = _innermost(host, mid)
        named.append([f"{where}: {doing}" if doing else where,
                      (b - a) * 1e-9])
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "device_ops": [[n, d * 1e-9 / len(devices)] for n, d in ranked],
            "idle_gaps": named}


def reduce_dir(trace_dir: str) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    return reduce_planes(ProfileData.from_file(max(files,
                                                   key=os.path.getmtime))
                         .planes)
