"""One run of one cell: set-up, the measured window, the comparison.

Everything that belongs to one configuration, traffic mix or metric sits in
files of its own, found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment (rules, generator and
  its parameters, store dtype, executor, reference);
* ``bench/rules/<rules>.dl``: the rule text;
* ``bench/generators/<generator>.py``: ``generate(config, seed)`` -> host
  arrays of base facts;
* ``bench/reference/<reference>.py``: ``evaluate(rules_text, tables)``,
  the plain reference;
* ``bench/traffic/<traffic>.json``: the mix's parameters, and under
  ``driver`` the name of the code that drives them;
* ``bench/drivers/<driver>.py``: ``run(jax, system, tables, mix, seconds,
  seed, compiles, trace_dir)``, set-up's warm work and the window; it
  chooses which of the engine's entries it drives;
* ``bench/metrics/<metric>.py``: ``read(ctx)`` -> the number, or None
  where the run has nothing to read.

A mix that an existing driver can drive is a data file alone.  The system
under test is reached through :class:`System` alone, so a test can put a
broken one in its place.
"""
from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time
import types

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with everything its files
    hold: ``cell``, ``config`` (the file's contents), ``mix``, ``rules``
    (text), ``generator``, ``reference``, ``driver`` (modules) and the
    ``end_to_end``
    and ``per_layer`` metric entries that the cell reports."""
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = _json(os.path.join(ROOT, entry["file"]))
    with open(os.path.join(BENCH, "rules", config["rules"] + ".dl")) as f:
        rules = f.read()

    def reports(metric):
        return name in metric.get("workloads", (name,))

    mix = _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return {
        "cell": cell,
        "config": config,
        "mix": mix,
        "rules": rules,
        "driver": importlib.import_module("bench.drivers." + mix["driver"]),
        "generator": importlib.import_module(
            "bench.generators." + config["generator"]),
        "reference": importlib.import_module(
            "bench.reference." + config["reference"]),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
    }


class System:
    """The program under test as every driver reaches it: the engine's
    entries (``engine``), the rules parsed, the configuration's store dtype
    and mode, and what can be read of the program's state.  Which entry a
    pass drives is the driver's choice."""

    def __init__(self, config: dict, rules: str):
        for k, v in config["executor"].items():
            os.environ[k] = v
        from repro.core.terms import parse_program
        from repro.engine import ops, plan
        from repro.engine.materialize import EngineKB, materialize
        self._ops, self._plan = ops, plan
        self.engine = types.SimpleNamespace(EngineKB=EngineKB,
                                            materialize=materialize)
        self.program = parse_program(rules)
        # the rules' own predicates: the engine adds internal ones
        # (``p~aux``) that are no part of the answer
        self.preds = {a.pred for r in self.program.rules
                      for a in (r.head, *r.body)}
        self.dtype = np.dtype(config["store_dtype"])
        self.mode = config["mode"]

    @staticmethod
    def buffers(kb):
        return [r.data for r in kb.rels.values()]

    def n_facts(self, kb) -> int:
        return sum(r.count for p, r in kb.rels.items() if p in self.preds)

    @staticmethod
    def left_executor(stats) -> bool:
        """The pass fell back from the fused executor or spilled."""
        return stats.extra.get("fused") is not True or \
            "spilled" in stats.extra

    def counters(self) -> dict:
        s = self._ops.HOST_SYNC_STATS
        return {"host_pulls": s.fused_pulls + s.count_pulls,
                "overflow_retries": s.fused_retries}

    def plan_memo(self) -> dict:
        return dict(self._plan._CAP_MEMO)

    def host_rows(self, kb):
        """({pred: ndarray of dictionary ids}, decode) on the host.  The
        whole padded buffer is copied and cut on the host: a slice on the
        device would compile a program per shape."""
        return ({p: np.asarray(r.data)[:r.count] for p, r in kb.rels.items()
                 if p in self.preds}, kb.dict.decode)


class Compiles:
    """Executables that JAX builds or loads from its persistent cache,
    counted through ``jax.monitoring`` (backend compile events wrap both)."""

    def __init__(self, jax):
        self.n = self.hits = 0
        self.secs = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)
        self._jax.monitoring.unregister_event_listener(self._on_event)


def enable_compile_cache(jax):
    """JAX's persistent cache at one fixed directory in the checkout, for
    every program however quick to compile, so only a checkout's first run
    compiles."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def peak_bytes(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def check_answers(ref, kept_rows, facts) -> dict:
    """The numbers that decide ``correct``: the largest ``missing`` and
    ``extra`` over the kept passes, and how many passes' fact counts differ
    from the reference's."""
    from bench.compare import compare, to_ranks
    rank = {t: i for i, t in enumerate(ref["terms"].tolist())}
    worst = {"missing": 0, "extra": 0}
    for rows, decode in kept_rows:
        got = {p: to_ranks(r, decode, rank) for p, r in rows.items()}
        c = compare(ref, got)
        worst = {k: max(worst[k], c[k]) for k in worst}
    want = sum(len(np.unique(r, axis=0)) if len(r) else 0
               for r in ref["facts"].values())
    return {**worst, "count_off": sum(1 for n in facts if n != want)}


def read_metrics(entries, ctx) -> dict:
    out = {}
    for m in entries:
        reader = importlib.import_module("bench.metrics." + m["name"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(jax, loaded, seed, seconds, trace, t0, system=None) -> dict:
    """Everything after the look for a chip: the result line as a dict.
    ``t0`` is the process's start on the perf_counter clock.

    The driver's record of the window holds ``warm_passes``, ``attempted``,
    ``failed``, ``raised`` (passes with no answer), ``facts`` (each
    answered pass's fact count), ``kept`` (host rows of the passes kept
    for the comparison), ``base`` (the base facts those answer),
    ``spans``, ``counters`` and ``window`` (its start and end)."""
    config = loaded["config"]
    compiles = Compiles(jax)
    try:
        tables = loaded["generator"].generate(config, seed)
        system = system or System(config, loaded["rules"])
        load = loaded["driver"].run(jax, system, tables, loaded["mix"],
                                    seconds, seed, compiles,
                                    TRACE_DIR if trace else None)
    finally:
        compiles.close()
    setup_s = load["window"][0] - t0
    peak = peak_bytes(jax)
    log(f"set-up {setup_s:.3f} s ({load['warm_passes']} warm passes, "
        f"{compiles.n} executables, {compiles.hits} from the cache, "
        f"{compiles.secs:.3f} s); window {load['attempted']} passes")
    for name, spans in load.get("spans", {}).items():
        log(f"{name} ms each pass: "
            + " ".join(f"{1e3 * (b - a):.1f}" for a, b in spans))
    trace_red = None
    if trace:
        from bench import trace_reduce
        trace_red = trace_reduce.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    t_ref = time.perf_counter()
    ref = loaded["reference"].evaluate(loaded["rules"], load["base"])
    checks = check_answers(ref, load["kept"], load["facts"])
    checks["no_answer"] = load["raised"]
    log(f"reference and comparison {time.perf_counter() - t_ref:.3f} s, "
        f"{ref['rounds']} rounds")
    ctx = {"setup_s": setup_s, "peak_hbm_bytes": peak, "load": load,
           "trace": trace_red}
    entries = loaded["per_layer"] if trace else loaded["end_to_end"]
    devices = jax.devices()
    result = {
        "correct": bool(load["kept"]) and all(v == 0
                                              for v in checks.values()),
        "attempted": load["attempted"],
        "failed": load["failed"],
        "metrics": read_metrics(entries, ctx),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": peak},
    }
    if trace_red is not None:
        result["device"]["busy_s"] = trace_red["busy_s"]
        result["device"]["window_s"] = trace_red["window_s"]
        result["breakdown"] = {"device_ops": trace_red["device_ops"],
                               "idle_gaps": trace_red["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} {v} limit 0")
    return result
