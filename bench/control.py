#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, run on the chip.

Usage, from the root of a checkout::

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed, one run of the cell as committed and one with the control
in the program's place.  The configurations state no numeric precision
(terms are exact ids in any store width that holds them), so the control
breaks the guarantee they do state, the least model: it is the program's
own ``max_rounds`` path, stopped before the last round that derives a
fact.  Each run prints its numbers compared (``missing``, ``extra``,
``count_off``, ``no_answer``); a control run has to come out not correct.
The benchmark's own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import System  # noqa: E402


class StopShort(System):
    """The program stopped one productive round short of its fixpoint.

    Its first pass runs to the fixpoint and learns the round count R (the
    last round derives nothing); every later pass stops at R - 2."""

    def __init__(self, config, rules):
        super().__init__(config, rules)
        self._full, self.rounds = self.engine.materialize, None
        self.engine.materialize = self._stop_short

    def _stop_short(self, kb, **kw):
        if self.rounds is None:
            stats = self._full(kb, **kw)
            self.rounds = stats.rounds
            return stats
        return self._full(kb, max_rounds=self.rounds - 2, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, cls in (("program", System), ("control", StopShort)):
            loaded = harness.load_cell(args.workload)
            system = cls(loaded["config"], loaded["rules"])
            r = harness.run_cell(jax, loaded, seed, args.seconds, False,
                                 time.perf_counter(), system=system)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "run": name, "correct": r["correct"],
                              "attempted": r["attempted"],
                              "checks": {k: v["value"] for k, v in
                                         r["checks"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
