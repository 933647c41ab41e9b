"""Scale trajectory — the sweep behind ``BENCH_scale.json``.

Materializes wide-TC (``tc_wide_chunks``: disjoint 4-edge chains, closure =
3.5x the base, fixpoint depth 4 regardless of size) through the fused
executor at 10^5 / 10^6 / 10^7 total facts (10^8 behind ``--huge``), on a
2x2 grid per size: store dtype (narrow int32 vs int64) x Pallas kernels
(``REPRO_USE_PALLAS`` 0/1).

Each cell runs in its own subprocess because both axes are locked at first
jax import (``JAX_ENABLE_X64`` for the int64 store; the Pallas flag is read
when the kernels first trace) and because ``ru_maxrss`` is a process
high-water mark — per-cell subprocesses give an honest peak_rss_mb per
configuration.  Inside a cell: streamed ingest via ``EngineKB.from_stream``
(timed separately as ingest throughput), one cold materialization (its
capacity-doubling recompiles are the reported ``cold_retries``), warm passes
until no planned capacity in ``plan._CAP_MEMO`` moved, then a timed
steady-state pass which must complete with ZERO overflow retries
(``warm_retries`` — the CI gate).  The timed pass also records the engine's
sort-pass counters and the roofline unit costs (bytes/flops-per-fact per op
class — sort / probe / absorb — plus the fused round and fixpoint programs,
via the trip-count-aware HLO walk in ``analysis.roofline``).

Acceptance hooks: every cell at a size must reach the exact closed-form
closure count (``tc_wide_total`` — fact parity across dtypes and kernel
paths), and at the largest size the narrow store's peak_rss_mb must come in
well under the int64 store's (the ``scale.rss_reduction.*`` rows).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["REPRO_STORE_DTYPE"] = %(dtype)r
    os.environ["REPRO_USE_PALLAS"] = %(pallas)r
    os.environ["REPRO_FUSED"] = "1"
    if %(dtype)r == "int64":
        os.environ["JAX_ENABLE_X64"] = "1"
    import sys, json, time, resource
    sys.path.insert(0, %(src)r)
    import jax
    from repro import compile_cache
    compile_cache.enable()
    import numpy as np
    from repro.data.kb_sources import TC, tc_wide_chunks, tc_wide_total
    from repro.engine import ops, plan
    from repro.engine.materialize import EngineKB, materialize

    n_chains = %(n_chains)d
    t0 = time.perf_counter()
    kb = EngineKB.from_stream(TC, tc_wide_chunks(n_chains))
    for r in kb.rels.values():
        jax.block_until_ready(r.data)
    ingest_s = time.perf_counter() - t0
    base_rows = sum(r.count for r in kb.rels.values())
    # materialize() only rebinds kb.rels entries (buffers are immutable on
    # CPU; nothing is donated), so restoring the dict gives a fresh pass
    # without re-paying ingest
    base_rels = dict(kb.rels)

    def run_pass():
        kb.rels = dict(base_rels)
        st = materialize(kb, mode="tg")
        for r in kb.rels.values():
            jax.block_until_ready(r.data)
        return st

    # cold pass: capacity guesses double-and-recompile (reported, not gated)
    ops.HOST_SYNC_STATS.reset()
    t0 = time.perf_counter()
    st = run_pass()
    cold_s = time.perf_counter() - t0
    cold_retries = ops.HOST_SYNC_STATS.fused_retries

    # warm until the capacity memo is stable (geometric tail growth means a
    # fixed warm count is not enough)
    prev = sorted((str(k), v) for k, v in plan._CAP_MEMO.items())
    warm_passes = 0
    for _ in range(3):
        run_pass()
        warm_passes += 1
        snap = sorted((str(k), v) for k, v in plan._CAP_MEMO.items())
        if snap == prev:
            break
        prev = snap

    ops.HOST_SYNC_STATS.reset()
    ops.SORT_STATS.reset()
    t0 = time.perf_counter()
    st = run_pass()
    warm_s = time.perf_counter() - t0
    warm_retries = ops.HOST_SYNC_STATS.fused_retries
    ss = ops.SORT_STATS

    facts = sum(kb.rels[p].count for p in kb.rels if "~" not in p)
    expected = tc_wide_total(n_chains)

    from repro.analysis.roofline import (engine_fused_roofline,
                                         engine_op_roofline)
    fused_roof = engine_fused_roofline(kb, facts)
    max_rows = max(r.count for r in kb.rels.values())
    op_roof = engine_op_roofline(max_rows)

    out = {
        "n_chains": n_chains, "base_rows": base_rows,
        "facts": facts, "expected": expected,
        "parity": int(facts == expected),
        "rounds": st.rounds, "triggers": st.triggers,
        "derived": st.derived,
        "ingest_s": ingest_s,
        "ingest_rows_per_s": base_rows / max(ingest_s, 1e-9),
        "cold_s": cold_s, "cold_retries": cold_retries,
        "warm_passes": warm_passes,
        "seconds": warm_s,
        "facts_per_s": facts / max(warm_s, 1e-9),
        "warm_retries": warm_retries,
        "sort_lexsort": ss.lexsort, "sort_key": ss.key_sort,
        "sort_merges": ss.merges, "sort_skipped": ss.skipped,
        "planned_rows": int(sum(plan._CAP_MEMO.values())),
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1),
        "roofline": {"ops": op_roof, "fused": fused_roof},
    }
    print("RESULT " + json.dumps(out))
""")

_GRID = (("int32", "0"), ("int32", "1"), ("int64", "0"), ("int64", "1"))

# tc_wide_total(W) = 14 * W at chain_len=4 (4 base edges + 10 closure facts
# per chain), so W = size // 14 hits the size to within one chain
_SIZES = ((10 ** 5, "1e5"), (10 ** 6, "1e6"), (10 ** 7, "1e7"))
_HUGE = (10 ** 8, "1e8")


def _cell(size: int, dtype: str, pallas: str) -> dict:
    script = _SCRIPT % {"dtype": dtype, "pallas": pallas, "src": _SRC,
                        "n_chains": size // 14}
    env = {k: v for k, v in os.environ.items()
           if k not in ("REPRO_STORE_DTYPE", "REPRO_USE_PALLAS",
                        "REPRO_FUSED", "REPRO_DIST", "JAX_ENABLE_X64")}
    try:
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=14400)
    except subprocess.TimeoutExpired:
        raise _CellFailed(
            f"scale cell size={size} dtype={dtype} pallas={pallas} "
            "timed out (14400 s)", reason="timeout")
    if r.returncode != 0:
        raise _CellFailed(
            f"scale cell size={size} dtype={dtype} pallas={pallas} failed:\n"
            + r.stderr[-3000:], reason="subprocess_error")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


class _CellFailed(RuntimeError):
    """One grid cell died (timeout / OOM-kill / crash).  The sweep emits a
    failed row and keeps going: a dead interpret-mode cell at the end of a
    multi-hour sweep must not discard every completed cell before it."""

    def __init__(self, msg: str, reason: str):
        super().__init__(msg)
        self.reason = reason


def _emit_roofline(prefix: str, roof: dict) -> None:
    ops_r = roof.get("ops") or {}
    for klass in ("sort", "probe", "absorb"):
        c = ops_r.get(klass)
        if c:
            emit(f"{prefix}.roofline.{klass}", 0.0, 0,
                 flops_per_fact=round(c["flops_per_fact"], 2),
                 bytes_per_fact=round(c["bytes_per_fact"], 2),
                 peak_rss_mb=0)
    for prog, c in (roof.get("fused") or {}).items():
        emit(f"{prefix}.roofline.fused_{prog}", 0.0, 0,
             flops_per_fact=round(c["flops_per_fact"], 2),
             bytes_per_fact=round(c["bytes_per_fact"], 2),
             intensity=round(c["intensity_flops_per_byte"], 3),
             sort_ops=c["sort_ops_static"],
             peak_rss_mb=0)


def run(smoke: bool = False, huge: bool = False):
    sizes = _SIZES[:1] if smoke else _SIZES + ((_HUGE,) if huge else ())
    for size, label in sizes:
        cells = {}
        for dtype, pallas in _GRID:
            if size >= 10 ** 7 and dtype == "int64" and pallas == "1":
                # interpret-mode Pallas on an int64 store costs ~450 s per
                # pass at 10^6 (no packed keys, double-width rows) — the
                # extrapolated 10^7 cell blows the subprocess budget.  A/B
                # coverage at this size stays: pallas 0/1 via the int32
                # pair, int64-vs-narrow via the pallas=0 pair.
                emit(f"scale.tcwide{label}.{dtype}.pallas{pallas}.skipped",
                     0.0, 0, reason="interpret_mode_cell_budget",
                     peak_rss_mb=0)
                continue
            try:
                rec = _cell(size, dtype, pallas)
            except _CellFailed as e:
                print(f"FAILED {e}", file=sys.stderr)
                emit(f"scale.tcwide{label}.{dtype}.pallas{pallas}.failed",
                     0.0, 0, reason=e.reason, peak_rss_mb=0)
                continue
            cells[(dtype, pallas)] = rec
            if not rec["parity"]:
                raise RuntimeError(
                    f"fact parity broken at size={size} dtype={dtype} "
                    f"pallas={pallas}: {rec['facts']} != {rec['expected']}")
            prefix = f"scale.tcwide{label}.{dtype}.pallas{pallas}"
            emit(prefix, rec["seconds"], rec["derived"],
                 facts=rec["facts"], parity=rec["parity"],
                 facts_per_s=round(rec["facts_per_s"]),
                 ingest_rows_per_s=round(rec["ingest_rows_per_s"]),
                 cold_s=round(rec["cold_s"], 3),
                 cold_retries=rec["cold_retries"],
                 warm_retries=rec["warm_retries"],
                 warm_passes=rec["warm_passes"],
                 rounds=rec["rounds"],
                 sort_lexsort=rec["sort_lexsort"],
                 sort_key=rec["sort_key"],
                 sort_merges=rec["sort_merges"],
                 sort_skipped=rec["sort_skipped"],
                 planned_rows=rec["planned_rows"],
                 peak_rss_mb=rec["peak_rss_mb"])
            _emit_roofline(prefix, rec["roofline"])
        for pallas in ("0", "1"):
            # a grid cell may have been skipped (int64/pallas1 at >=10^7);
            # only reduce over pairs where both dtypes actually ran
            if ("int64", pallas) not in cells or ("int32", pallas) not in cells:
                continue
            wide = cells[("int64", pallas)]["peak_rss_mb"]
            narrow = cells[("int32", pallas)]["peak_rss_mb"]
            emit(f"scale.rss_reduction.{label}.pallas{pallas}", 0.0, 0,
                 rss_int64_mb=wide, rss_int32_mb=narrow,
                 reduction_pct=round(100.0 * (wide - narrow)
                                     / max(wide, 1e-9), 1),
                 peak_rss_mb=0)


if __name__ == "__main__":
    import benchmarks.common  # noqa: F401  (sys.path side effect)
    run(smoke="--smoke" in sys.argv, huge="--huge" in sys.argv)
