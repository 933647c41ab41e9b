"""The closed-loop load driver: one client, passes back to back.

A pass is the user's load path: a fresh ``EngineKB.from_arrays`` over the
cell's host arrays (so no donated buffer is reused), then ``materialize``
to fixpoint, every store ready.  A mix file that names this driver gives:

* ``clients``: 1, the only count this driver drives;
* ``warm_passes_max``: the most warm passes set-up runs;
* ``traced_passes``: how many of the window's first passes ``--trace 1``
  traces;
* ``sampled_pass_range``: one pass drawn from the seed among this many
  first passes is kept for the comparison, besides the last.
"""
from __future__ import annotations

import contextlib
import shutil
import time

import numpy as np

from bench.harness import log


def run_pass(jax, system, tables, spans=None, annotate=False):
    """One pass; returns (kb, stats) and appends its ingest and materialize
    spans (perf_counter seconds) to ``spans``."""
    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    engine = system.engine
    t0 = time.perf_counter()
    with span("bench.ingest"):
        kb = engine.EngineKB.from_arrays(system.program, tables,
                                         dtype=system.dtype)
        jax.block_until_ready(system.buffers(kb))
    t1 = time.perf_counter()
    with span("bench.materialize"):
        stats = engine.materialize(kb, mode=system.mode)
        jax.block_until_ready(system.buffers(kb))
    t2 = time.perf_counter()
    if spans is not None:
        spans["ingest"].append((t0, t1))
        spans["materialize"].append((t1, t2))
    return kb, stats


def run(jax, system, tables, mix, seconds, seed, compiles, trace_dir=None):
    """Set-up: warm passes until the planner's capacity memo and the count
    of executables both stand still over a pass.  Window: passes until
    ``seconds`` have elapsed; the pass in flight then completes and counts.
    With ``trace_dir``, the window's first passes run under the profiler.

    Returns the harness's record of the window (see ``harness.run_cell``):
    every pass answers the same base facts, ``tables``."""
    if mix["clients"] != 1:
        raise ValueError("the closed-loop driver drives one client")
    warm = 0
    while True:
        memo, n0 = system.plan_memo(), compiles.n
        run_pass(jax, system, tables)
        warm += 1
        settled = system.plan_memo() == memo and compiles.n == n0
        if (settled and warm >= 2) or warm >= mix["warm_passes_max"]:
            break
    keep = int(np.random.default_rng(seed).integers(
        mix["sampled_pass_range"]))
    out = {"warm_passes": warm, "attempted": 0, "failed": 0, "raised": 0,
           "facts": [], "kept": {}, "base": tables,
           "spans": {"ingest": [], "materialize": []}}
    traced = mix["traced_passes"] if trace_dir else 0
    c0, n0 = system.counters(), compiles.n
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_start = time.perf_counter()
    i = 0
    while True:
        if traced and i == 0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans only, no py calls
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        out["attempted"] += 1
        try:
            kb, stats = run_pass(jax, system, tables, out["spans"],
                                 annotate=i < traced)
        except Exception as e:     # a pass that raises has no answer
            log(f"pass {i} raised {type(e).__name__}: {e}")
            out["failed"] += 1
            out["raised"] += 1
            kb = None
        else:
            out["facts"].append(system.n_facts(kb))
            if system.left_executor(stats):
                out["failed"] += 1
        if traced and i == traced - 1:
            jax.profiler.stop_trace()
        done = time.perf_counter() - t_start >= seconds
        if kb is not None and i == keep and not done:
            # to the host at once, so no seed holds more on the device
            out["kept"][i] = system.host_rows(kb)
        i += 1
        if done:
            break
        kb = None
    t_end = time.perf_counter()
    if kb is not None:
        out["kept"][i - 1] = system.host_rows(kb)
        kb = None
    if traced and i < traced:
        jax.profiler.stop_trace()
    c1 = system.counters()
    out["kept"] = [rows for _, rows in sorted(out["kept"].items())]
    out["window"] = (t_start, t_end)
    out["counters"] = {k: c1[k] - c0[k] for k in c1}
    out["counters"]["compiles_in_window"] = compiles.n - n0
    return out
