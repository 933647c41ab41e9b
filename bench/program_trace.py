"""Reduce a JAX profiler trace to the program's own steps: device time per
core scope, device idle time by the host step it fell in, host encode time.

The program names its steps itself (``repro.engine``):

* device scopes: every core runs under a ``jax.named_scope`` (``tg.sort``,
  ``tg.join``, ``tg.probe``, ``tg.merge``, ``tg.compact``, ``tg.exchange``),
  carried in each op's ``op_name`` metadata;
* host spans: ``jax.profiler.TraceAnnotation`` events named ``tg.*``
  (``tg.ingest``, ``tg.encode``, ``tg.materialize``, ``tg.round``,
  ``tg.fixpoint``, ``tg.fold``, ``tg.pull``, ``tg.checkpoint``) on the
  profiler's clock.

On a TPU v5e the trace carries an op's ``op_name`` as the ``tf_op`` stat of
its event metadata (``<op_name>:<op type>``), for every op but a ``while``.
``jax.profiler.ProfileData`` does not expose metadata stats, so the
``.xplane.pb`` is read here with the XSpace schema's few fields this needs.
A ``while`` takes the longest common path of the ops nested in it (the ops
of its body and condition extend its own path), and an op with no path at
all (a copy XLA inserted) takes its enclosing op's scope.

Device time is self time, on the ``XLA Ops`` line of the first device: an
op's interval less the ops nested in it on the same line (a ``while`` and
its body ops are all on that line), so scope times and unscoped time add up
to busy time.  Where scopes nest, the outermost ``tg.`` component of the
path names the op: a probe inside ``tg.merge`` is merge work.

Idle time is the first device's gaps inside the window that
``bench/trace_reduce.py`` uses (the first to the last end of the
benchmark's ``bench.*`` spans), each instant charged to the innermost
``tg.*`` host span open then: ``tg.pull`` is ``sync`` (a round trip), any
other step is ``host`` (the device waits on host work), and no step is
``other`` (the benchmark's own code between the program's calls).

Every time is per pass: divided by the number of ``bench.materialize``
spans in the trace.

``python3 -m bench.program_trace --workload <cell> --seed <n>``, from the
root of a checkout, runs a cell's traced passes and prints the reduction.
"""
from __future__ import annotations

import glob
import os
from types import SimpleNamespace as NS

from bench.trace_reduce import (DEVICE_PREFIX, HOST_PLANE, OPS_LINE,
                                SPAN_PREFIX, TOP, _clip, _union)

PREFIX = "tg."
PULL = "tg.pull"
ENCODE = "tg.encode"
PASS_SPAN = "bench.materialize"
CORES = ("tg.sort", "tg.join", "tg.probe", "tg.merge", "tg.compact",
         "tg.exchange")


# ---------------------------------------------------------------------------
# the trace's planes, metadata stats included
# ---------------------------------------------------------------------------
def _xspace_class():
    """The message class of the XSpace fields read here (the schema is
    ``tsl/profiler/protobuf/xplane.proto``; unread fields are skipped).  A
    field's type is a scalar type, a message name, or ``[name]`` for a
    repeated message."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    i64, u64, f64, s = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE,
                        F.TYPE_STRING)
    for name, fields in (
            ("XStat", [("metadata_id", 1, i64), ("double_value", 2, f64),
                       ("uint64_value", 3, u64), ("int64_value", 4, i64),
                       ("str_value", 5, s), ("ref_value", 7, u64)]),
            ("XEvent", [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                        ("duration_ps", 3, i64), ("stats", 4, ["XStat"])]),
            ("XLine", [("name", 2, s), ("timestamp_ns", 3, i64),
                       ("events", 4, ["XEvent"])]),
            ("XEventMetadata", [("name", 2, s), ("stats", 5, ["XStat"])]),
            ("XStatMetadata", [("name", 2, s)]),
            ("EventMetadataEntry", [("key", 1, i64),
                                    ("value", 2, "XEventMetadata")]),
            ("StatMetadataEntry", [("key", 1, i64),
                                   ("value", 2, "XStatMetadata")]),
            ("XPlane", [("name", 2, s), ("lines", 3, ["XLine"]),
                        ("event_metadata", 4, ["EventMetadataEntry"]),
                        ("stat_metadata", 5, ["StatMetadataEntry"])]),
            ("XSpace", [("planes", 1, ["XPlane"])])):
        m = f.message_type.add(name=name)
        for fname, number, typ in fields:
            fd = m.field.add(name=fname, number=number,
                             label=F.LABEL_REPEATED if isinstance(typ, list)
                             else F.LABEL_OPTIONAL)
            if isinstance(typ, int):
                fd.type = typ
            else:
                fd.type = F.TYPE_MESSAGE
                fd.type_name = ".bench_xplane." + (
                    typ[0] if isinstance(typ, list) else typ)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_value(stat, stat_names):
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value)
    return stat.int64_value or stat.uint64_value or stat.double_value


def read_planes(path: str):
    """The planes of one ``.xplane.pb``, shaped as ``ProfileData``'s
    (``name``; ``lines`` with ``name``; ``events`` with ``name``,
    ``start_ns``, ``duration_ns``), each event's ``stats`` a dict of its
    own and its metadata's stats (device ops only, where ``tf_op`` is)."""
    with open(path, "rb") as fh:
        space = _xspace_class().FromString(fh.read())
    planes = []
    for plane in space.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            stats = ({stat_names.get(st.metadata_id):
                      _stat_value(st, stat_names) for st in e.value.stats}
                     if device else {})
            meta[e.key] = (e.value.name, stats)
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            t0 = line.timestamp_ns
            events = []
            for ev in line.events:
                name, stats = meta.get(ev.metadata_id, ("", {}))
                events.append(NS(name=name, start_ns=t0 + ev.offset_ps * 1e-3,
                                 duration_ns=ev.duration_ps * 1e-3,
                                 stats=stats))
            lines.append(NS(name=line.name, events=events))
        planes.append(NS(name=plane.name, lines=lines))
    return planes


# ---------------------------------------------------------------------------
# device: self time per scope
# ---------------------------------------------------------------------------
def _path(stats):
    """An op's ``op_name`` path as its components, or None."""
    tf_op = (stats or {}).get("tf_op")
    if not tf_op:
        return None
    return tuple(tf_op.rsplit(":", 1)[0].split("/"))


def _outermost(path):
    for part in path or ():
        if part.startswith(PREFIX):
            return part
    return None


def _common(paths):
    out = paths[0]
    for p in paths[1:]:
        n = 0
        while n < min(len(out), len(p)) and out[n] == p[n]:
            n += 1
        out = out[:n]
    return out


def scope_self_ns(ops, lo, hi):
    """``{scope or None: self time in ns inside [lo, hi)}`` over the ops of
    one ``XLA Ops`` line, ``ops`` being (start, end, stats)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    parent = [None] * len(ops)
    children = [[] for _ in ops]
    stack = []
    for i in order:
        a = ops[i][0]
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            children[stack[-1]].append(i)
        stack.append(i)
    paths = [_path(op[2]) for op in ops]
    for i in reversed(order):       # children before their parents
        if paths[i] is None:
            known = [paths[c] for c in children[i] if paths[c]]
            if known:
                paths[i] = _common(known)
    scope = [None] * len(ops)
    for i in order:                 # parents before their children
        p = parent[i]
        scope[i] = (scope[p] if p is not None else None) or \
            _outermost(paths[i])
    out = {}
    for i in order:
        a, b = ops[i][0], ops[i][1]
        inner = _union(_clip([(ops[c][0], ops[c][1]) for c in children[i]],
                             a, b))
        t, own = a, []
        for ca, cb in inner + [[b, b]]:
            if ca > t:
                own.append((t, ca))
            t = max(t, cb)
        self_ns = sum(y - x for x, y in _clip(own, lo, hi))
        if self_ns:
            out[scope[i]] = out.get(scope[i], 0.0) + self_ns
    return out


# ---------------------------------------------------------------------------
# host: innermost tg.* step at each instant
# ---------------------------------------------------------------------------
def _steps(spans):
    """Sorted, disjoint (start, end, name) segments naming the innermost
    ``tg.*`` span open over each stretch (the program opens them on one
    thread, properly nested)."""
    out, stack, t = [], [], None
    bounds = []
    for a, b, name in spans:
        bounds.append((a, 1, -b, name))
        bounds.append((b, 0, 0, name))
    for x, opening, neg_end, name in sorted(bounds):
        if stack and t is not None and x > t:
            out.append((t, x, stack[-1][1]))
        if opening:
            stack.append((-neg_end, name))
        else:
            for k in range(len(stack) - 1, -1, -1):
                if stack[k][1] == name and stack[k][0] == x:
                    del stack[k]
                    break
        t = x
    return out


def _charge(gaps, steps):
    """``{step or None: ns}`` of the gaps by the innermost step."""
    out, j = {}, 0
    for a, b in gaps:
        covered = 0
        while j < len(steps) and steps[j][1] <= a:
            j += 1
        k = j
        while k < len(steps) and steps[k][0] < b:
            x, y = max(a, steps[k][0]), min(b, steps[k][1])
            if y > x:
                out[steps[k][2]] = out.get(steps[k][2], 0) + y - x
                covered += y - x
            k += 1
        if b - a > covered:
            out[None] = out.get(None, 0) + (b - a) - covered
    return out


def reduce_planes(planes) -> dict | None:
    """The program's steps in one trace; None when it holds no device op
    or no benchmark span.  Times are ms per pass:

    * ``scope_ms``: device self time per core scope (``tg.sort`` ...) and
      ``unscoped``; None when no op carries a ``tg.`` scope;
    * ``idle_ms``: device idle time ``host``, ``sync`` and ``other``, and
      ``idle_steps_ms`` by the innermost step; None without ``tg.*`` spans;
    * ``encode_ms``: host time in ``tg.encode``, or None without it;
    * ``passes``, ``pulls`` (``tg.pull`` spans in the window),
      ``idle_gaps`` (the longest gaps named by their step, seconds)."""
    bench, tg, device = [], [], None
    for plane in planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.startswith(SPAN_PREFIX):
                        bench.append(ev)
                    elif e.name.startswith(PREFIX):
                        tg.append(ev)
        elif device is None and plane.name.startswith(DEVICE_PREFIX):
            ops = [(e.start_ns, e.start_ns + e.duration_ns,
                    getattr(e, "stats", None))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                device = ops
    if not bench or device is None:
        return None
    lo = min(a for a, _, _ in bench)
    hi = max(b for _, b, _ in bench)
    passes = sum(1 for _, _, n in bench if n == PASS_SPAN) or 1
    tg = [s for s in tg if s[1] > lo and s[0] < hi]

    def ms(ns):
        return ns * 1e-6 / passes

    by_scope = scope_self_ns(device, lo, hi)
    scoped = any(k for k in by_scope)
    scope_ms = ({**{c: ms(by_scope.get(c, 0.0)) for c in CORES},
                 "unscoped": ms(by_scope.get(None, 0.0))}
                if scoped else None)
    busy = _union(_clip([(a, b) for a, b, _ in device], lo, hi))
    gaps, t = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    idle_ms = steps_ms = None
    named = []
    if tg:
        steps = _steps(tg)
        charged = _charge(gaps, steps)
        sync = charged.get(PULL, 0)
        other = charged.get(None, 0)
        idle_ms = {"host": ms(sum(charged.values()) - sync - other),
                   "sync": ms(sync), "other": ms(other)}
        steps_ms = {k or "none": ms(v) for k, v in
                    sorted(charged.items(), key=lambda kv: -kv[1])}
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
            step = _charge([(a, b)], steps)
            top = max(step, key=step.get)
            named.append([top or "none", (b - a) * 1e-9])
    encode = [(a, b) for a, b, n in tg if n == ENCODE]
    return {"passes": passes,
            "busy_ms": ms(sum(b - a for a, b in busy)),
            "scope_ms": scope_ms,
            "idle_ms": idle_ms,
            "idle_steps_ms": steps_ms,
            "encode_ms": (ms(sum(b - a for a, b in _union(
                _clip(encode, lo, hi)))) if encode else None),
            "pulls": sum(1 for _, _, n in tg if n == PULL),
            "idle_gaps": named}


def reduce_dir(trace_dir: str) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    return reduce_planes(read_planes(max(files, key=os.path.getmtime)))


def main(argv=None) -> int:
    """One cell's set-up and traced passes, as ``bench/run.py --trace 1``
    runs them, and this module's reduction of their trace printed as one
    JSON line with the window's counters.  It compares no answer and
    reports no metric of ``BENCHMARK.json``."""
    import argparse
    import json
    import sys

    from bench import harness
    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    loaded = harness.load_cell(args.workload)
    harness.enable_compile_cache(jax)
    compiles = harness.Compiles(jax)
    try:
        system = harness.System(loaded["config"], loaded["rules"])
        load = loaded["driver"].run(
            jax, system, loaded["generator"].generate(loaded["config"],
                                                      args.seed),
            loaded["mix"], args.seconds, args.seed, compiles,
            harness.TRACE_DIR)
    finally:
        compiles.close()
    out = {"attempted": load["attempted"], "counters": load["counters"],
           "program": reduce_dir(harness.TRACE_DIR)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
