#!/usr/bin/env python3
"""Chip smoke test: the materializer's main path, end to end, on one TPU.

Usage (from the root of a checkout)::

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded executor on four chips

Everything runs in this one process, through the entry points a user calls
(``EngineKB.from_stream`` / ``EngineKB(...)`` and ``materialize``) on the
compiled executor (``REPRO_FUSED=1``):

1. LUBM-L against the symbolic chase: equal decoded fact sets.
2. int16 store: TC-WIDE at the largest size whose node ids fit int16.
3. ``REPRO_USE_PALLAS=1``: TC-WIDE at 10^6 facts through the Pallas sort
   and unique-mask kernels.
4. TC-WIDE (``STREAM_SCENARIOS["TC-WIDE"]``) at ``TC_FACTS`` total facts on
   an int32 store.

The device's memory in use and its peak so far are printed after each
phase; LUBM-L runs first, so the first peak is its own.

A TC-WIDE phase passes when its fact count equals ``tc_wide_total``
exactly (missing facts are listed when it does not) and the run stayed on
the fused executor without spilling.

``--four-chips`` runs only the sharded executor (``backend="dist"``) on a
four-device mesh and the single-device fused run it is compared with, on
TC-WIDE and LUBM-L; their fact sets must be equal.

Earlier lines report timings, compile counts and the device; the last line
is one JSON object, ``{"ok": true, "device": {...}}``.  Any failed check,
and a process whose JAX finds no TPU, exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from bench.harness import Compiles

# Cold runs are compile-bound (an XLA TPU sort of 2^16 rows or more takes
# 20-30 s to compile), so the sizes are those whose phases, measured cold
# on one v5e chip, add up to well under 20 minutes: see PERF.md.
TC_FACTS = 10 ** 7
TC_FACTS_FOUR_CHIPS = 5 * 10 ** 3
PALLAS_TC_FACTS = 10 ** 6
INT16_CHAINS = 6_500           # 5 node ids per chain: 32,500 < int16 PAD
LUBM_UNIVERSITIES = 12         # at 14 the stores pass 2^15 rows: see PERF.md


class CheckFailed(RuntimeError):
    pass


def check(cond, what):
    if not cond:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


def phase(name, compiles, fn):
    n0, s0 = compiles.n, compiles.secs
    t0 = time.perf_counter()
    print(f"[{name}]", flush=True)
    fn()
    print(f"[{name}] {time.perf_counter() - t0:.1f} s, "
          f"{compiles.n - n0} compiles ({compiles.secs - s0:.1f} s)",
          flush=True)


def facts_of(kb):
    """Encoded rows per predicate (stores are lexsorted, so two runs over
    one dictionary hold equal fact sets iff these arrays are equal)."""
    import numpy as np
    return {p: np.asarray(r.np_rows()) for p, r in sorted(kb.rels.items())}


def same_facts(a, b):
    import numpy as np
    return a.keys() == b.keys() and all(
        a[p].shape == b[p].shape and np.array_equal(a[p], b[p]) for p in a)


def tc_count(kb):
    return sum(r.count for p, r in kb.rels.items() if "~" not in p)


def run_tc_wide(total, dtype=None, n_chains=None):
    """Stream TC-WIDE in and materialize it; returns (kb, stats, chains)."""
    import jax
    from repro.data.kb_sources import TC, tc_wide_chunks
    from repro.engine.materialize import EngineKB, materialize
    n = n_chains if n_chains is not None else max(total // 14, 1)
    t0 = time.perf_counter()
    kb = EngineKB.from_stream(TC, tc_wide_chunks(n, dtype=dtype),
                              dtype=dtype)
    jax.block_until_ready([r.data for r in kb.rels.values()])
    t1 = time.perf_counter()
    st = materialize(kb, mode="tg")
    jax.block_until_ready([r.data for r in kb.rels.values()])
    t2 = time.perf_counter()
    print(f"  {n} chains: ingest {t1 - t0:.1f} s, materialize "
          f"{t2 - t1:.1f} s, {st.rounds} rounds, {st.triggers} triggers",
          flush=True)
    return kb, st, n


def missing_tc(kb, n, limit=5):
    """Up to ``limit`` closure facts T(a, b) of TC-WIDE that the store
    lacks, as node numbers (chain c holds nodes 5c .. 5c + 4)."""
    import numpy as np
    rows = kb.rels["T"].np_rows()
    ids = np.asarray([kb.dict.decode(int(i)) for i in np.unique(rows)])
    node = dict(zip(np.unique(rows).tolist(), ids.tolist()))
    have = {(node[int(a)], node[int(b)]) for a, b in rows}
    out = []
    for c in range(n):
        for a in range(5 * c, 5 * c + 4):
            for b in range(a + 1, 5 * c + 5):
                if (a, b) not in have:
                    out.append((a, b))
                    if len(out) == limit:
                        return out
    return out


def check_tc(kb, st, n, label):
    from repro.data.kb_sources import tc_wide_total
    got, want = tc_count(kb), tc_wide_total(n)
    if got != want and n <= 10 ** 5:
        print(f"  {label}: missing T facts (node pairs) {missing_tc(kb, n)}",
              flush=True)
    check(got == want, f"{label}: {got} facts == tc_wide_total = {want}")
    check(st.extra.get("fused") is True, f"{label}: fused executor")
    check("spilled" not in st.extra, f"{label}: no spill")


def one_chip(dev, compiles):
    import numpy as np
    from repro.core.chase import chase
    from repro.data.kb_sources import LUBM_L, lubm_facts
    from repro.engine.materialize import EngineKB, materialize

    def tc_big():
        kb, st, n = run_tc_wide(TC_FACTS)
        check_tc(kb, st, n, f"TC-WIDE {TC_FACTS:.0e} int32")

    def lubm():
        B = lubm_facts(n_univ=LUBM_UNIVERSITIES)
        t0 = time.perf_counter()
        want = chase(LUBM_L, B).facts | set(B)
        t1 = time.perf_counter()
        kb = EngineKB(LUBM_L, B)
        st = materialize(kb, mode="tg")
        print(f"  {len(B)} base facts: chase {t1 - t0:.1f} s, engine "
              f"{time.perf_counter() - t1:.1f} s", flush=True)
        check(st.extra.get("fused") is True, "LUBM-L: fused executor")
        check(kb.decode_facts() == want,
              f"LUBM-L: {len(want)} facts equal to the chase")

    def int16():
        kb, st, n = run_tc_wide(None, dtype=np.int16, n_chains=INT16_CHAINS)
        check(all(r.dtype == np.int16 for r in kb.rels.values()),
              "int16: every store is int16")
        check_tc(kb, st, n, "TC-WIDE int16")

    def pallas():
        os.environ["REPRO_USE_PALLAS"] = "1"
        try:
            kb, st, n = run_tc_wide(PALLAS_TC_FACTS)
        finally:
            os.environ.pop("REPRO_USE_PALLAS")
        check_tc(kb, st, n, f"TC-WIDE {PALLAS_TC_FACTS:.0e} Pallas")

    for name, fn in (("lubm-l", lubm), ("int16", int16), ("pallas", pallas),
                     ("tc-wide", tc_big)):
        phase(name, compiles, fn)
        stats = dev.memory_stats() or {}
        print(f"  bytes_in_use {stats.get('bytes_in_use')}, "
              f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
              flush=True)


def four_chips(compiles):
    from repro.data.kb_sources import LUBM_L, lubm_facts
    from repro.engine.distributed import materialize_distributed
    from repro.engine.materialize import EngineKB, materialize
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(4)

    def compare(label, build):
        kb = build()
        materialize(kb, mode="tg")
        ref = facts_of(kb)
        kb = build()
        st = materialize_distributed(kb, mode="tg", mesh=mesh)
        check(st is not None and st.extra.get("ndev") == 4,
              f"{label}: sharded executor on 4 devices")
        check("spilled" not in st.extra, f"{label}: no spill")
        got = facts_of(kb)
        check(same_facts(got, ref),
              f"{label}: {sum(len(r) for r in got.values())} facts equal "
              "to the single-device fused run")

    def tc():
        from repro.data.kb_sources import STREAM_SCENARIOS
        program, chunks = STREAM_SCENARIOS["TC-WIDE"]
        compare(f"TC-WIDE {TC_FACTS_FOUR_CHIPS:.0e}",
                lambda: EngineKB.from_stream(program,
                                             chunks(TC_FACTS_FOUR_CHIPS)))

    def lubm():
        B = lubm_facts(n_univ=LUBM_UNIVERSITIES)
        compare("LUBM-L", lambda: EngineKB(LUBM_L, B))

    phase("four-chips tc-wide", compiles, tc)
    phase("four-chips lubm-l", compiles, lubm)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded executor on four chips, against "
                         "the single-device fused run")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: {need} TPU devices needed, {len(devices)} found",
              file=sys.stderr)
        return 2
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no engine sources at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    os.environ["REPRO_FUSED"] = "1"
    from repro import compile_cache
    print(f"compile cache {compile_cache.enable()}", flush=True)
    compiles = Compiles(jax)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(compiles)
        else:
            one_chip(dev, compiles)
    except CheckFailed as e:
        print(f"FAILED: {e}", flush=True)
        return 1
    print(f"total {time.perf_counter() - t0:.1f} s, {compiles.n} compiles "
          f"({compiles.secs:.1f} s)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
