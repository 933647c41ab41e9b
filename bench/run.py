#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

Usage, from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, data from ``--seed``, warm passes) runs first; then the
cell's traffic runs for ``--seconds``; then the plain reference checks what
the window's passes produced.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` with ``--trace 1``, and ``checks``: each number compared with
its limit).  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a run whose first passes are traced.

Exits non-zero without that line where JAX finds no TPU, or fewer chips
than the cell asks for, or the checkout holds no engine sources.
"""
import time

T0 = time.perf_counter()    # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a non-negative whole number")

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no engine sources at {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import harness
    loaded = harness.load_cell(args.workload)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no accelerator: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    need = loaded["cell"]["chips"]
    if len(devices) < need:
        print(f"bench: {need} TPU chips needed, {len(devices)} found",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    result = harness.run_cell(jax, loaded, args.seed, args.seconds,
                              bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
