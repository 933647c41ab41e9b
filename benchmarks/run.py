"""Benchmark driver — one module per paper table.

Prints ``name,us_per_call,derived[,k=v...]`` CSV rows.  Each module warms the
jit caches with a small instance before timing (capacity-bucketed kernels are
compile-once-per-bucket).

``--smoke`` runs every table on tiny instances (seconds, not minutes) and
writes the rows to ``BENCH_smoke.json`` — the machine-readable perf
trajectory CI uploads as an artifact on every push.  ``--out FILE`` overrides
the JSON path (also usable without ``--smoke`` for full runs).

Whenever the ``tc`` table runs (it is part of the default set), its fused vs
two-phase rows are additionally written to ``BENCH_tc.json`` at the repo
root — the stable per-commit trajectory of the transitive-closure
benchmark: trigger counts, rounds, wall time, and host-sync counts for both
executors.

One process per chip: this script never touches JAX.  Each table runs in a
child process of its own (``--child``), which hands its rows back on its
last stdout line, so a table whose own children need the accelerator
(``scale``, ``dist``) never finds it held by an earlier table.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys

from benchmarks import (bench_chasebench, bench_datalog, bench_delta,
                        bench_dist, bench_fused, bench_linear, bench_rdfs,
                        bench_recovery, bench_scalability, bench_scale,
                        bench_triggers)
from benchmarks import common

TABLES = {
    "linear": bench_linear.run,          # paper Table 2
    "datalog": bench_datalog.run,        # paper Table 3
    "chasebench": bench_chasebench.run,  # paper Table 4
    "triggers": bench_triggers.run,      # paper Table 5 / 8a
    "rdfs": bench_rdfs.run,              # paper Table 6
    "scalability": bench_scalability.run,  # paper Table 7
    "tc": bench_fused.run,               # fused vs two-phase host syncs
    "dist": bench_dist.run,              # sharded executor scaling (ndev)
    "delta": bench_delta.run,            # incremental maintenance cost
    "scale": bench_scale.run,            # 10^5..10^8 dtype/pallas sweep
    "recovery": bench_recovery.run,      # checkpoint overhead + resume cost
}


_ROWS = "ROWS "


def _child(name: str, smoke: bool, huge: bool) -> None:
    """Run one table in this process; its rows go out as the last line."""
    from repro import compile_cache
    compile_cache.enable()
    if name == "scale":
        TABLES[name](smoke=smoke, huge=huge)
    else:
        TABLES[name](smoke=smoke)
    print(_ROWS + json.dumps(common.RESULTS), flush=True)


def _run_table(name: str, smoke: bool, huge: bool) -> list:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, "-m", "benchmarks.run", "--child", name]
    cmd += ["--smoke"] * smoke + ["--huge"] * huge
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(cmd, cwd=root, env=env, stdout=subprocess.PIPE,
                          text=True)
    lines = proc.stdout.splitlines()
    rows = [ln for ln in lines if ln.startswith(_ROWS)]
    for ln in lines:
        if not ln.startswith(_ROWS):
            print(ln, flush=True)
    if proc.returncode != 0 or not rows:
        raise SystemExit(f"[bench] table {name!r} failed "
                         f"(exit {proc.returncode})")
    return json.loads(rows[-1][len(_ROWS):])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("tables", nargs="*", choices=[[], *TABLES],
                    help="subset of tables (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instances; write BENCH_smoke.json")
    ap.add_argument("--out", default=None,
                    help="JSON output path (default BENCH_smoke.json "
                         "with --smoke, none otherwise)")
    ap.add_argument("--huge", action="store_true",
                    help="extend the scale sweep to 10^8 facts")
    ap.add_argument("--child", choices=list(TABLES), default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child(args.child, args.smoke, args.huge)
        return

    which = args.tables or list(TABLES)
    common.reset_results()
    print("name,us_per_call,derived,extra...", flush=True)
    for name in which:
        common.RESULTS.extend(_run_table(name, args.smoke, args.huge))

    def write_payload(path, rows, **extra):
        payload = {
            "mode": "smoke" if args.smoke else "full",
            "python": platform.python_version(),
            "use_pallas": os.environ.get("REPRO_USE_PALLAS", "0"),
            **extra,
            "results": rows,
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"[bench] wrote {len(rows)} rows to {path}", file=sys.stderr)

    out = args.out or ("BENCH_smoke.json" if args.smoke else None)
    if out:
        write_payload(out, common.RESULTS, tables=which)
    if "tc" in which:
        # smoke runs write a separate file so they never clobber the
        # committed full-run trajectory at BENCH_tc.json
        write_payload("BENCH_tc_smoke.json" if args.smoke
                      else "BENCH_tc.json",
                      [r for r in common.RESULTS
                       if r["name"].startswith("tc.")])
    if "dist" in which:
        # same convention for the distributed-executor scaling trajectory
        write_payload("BENCH_dist_smoke.json" if args.smoke
                      else "BENCH_dist.json",
                      [r for r in common.RESULTS
                       if r["name"].startswith("dist.")])
    if "delta" in which:
        # and for the incremental-maintenance cost trajectory
        write_payload("BENCH_delta_smoke.json" if args.smoke
                      else "BENCH_delta.json",
                      [r for r in common.RESULTS
                       if r["name"].startswith("delta.")])
    if "recovery" in which:
        # and for the checkpoint-overhead / resume-cost trajectory
        write_payload("BENCH_recovery_smoke.json" if args.smoke
                      else "BENCH_recovery.json",
                      [r for r in common.RESULTS
                       if r["name"].startswith("recovery.")])
    if "scale" in which:
        # and for the 10^5..10^8 dtype/pallas scale trajectory
        write_payload("BENCH_scale_smoke.json" if args.smoke
                      else "BENCH_scale.json",
                      [r for r in common.RESULTS
                       if r["name"].startswith("scale.")],
                      huge=args.huge)


if __name__ == "__main__":
    main()
