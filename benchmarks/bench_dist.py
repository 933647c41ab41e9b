"""Distributed-executor scaling table — the trajectory behind
``BENCH_dist.json``.

Runs deep-chain TC (the O(rounds)-vs-O(phases) host-sync scenario), a wide
random-graph TC (few rounds, big per-round joins — the scenario where
sharding the sort/merge work pays off), and LUBM-L through the sharded
shard_map executor at ndev in {1, 2, 4, 8} (smoke: {1, 2}).  Each shard
count runs in a subprocess (one process per chip; on the CPU platform the
shards are ``xla_force_host_platform_device_count`` host devices, which is
locked at first jax init), warms
until the capacity planner is stable (no cap in ``plan._CAP_MEMO`` moved on
the last run — the while_loop fixpoint doubles tails geometrically, so two
fixed warm passes are not enough), then times a steady-state run.

Every subprocess also times the fused single-device executor
(``REPRO_FUSED=1``) on the same instance under the same warm discipline: it
is both the parity reference and the baseline behind ``speedup_vs_fused``
(fused seconds / dist seconds, same process so thread conditions match).
The ndev=1 subprocess additionally emits one ``dist.fused_base.*`` row per
scenario so the baseline wall time lands in the table.

Reported per dist row: wall time, derived/total facts, rounds, triggers,
parity vs fused, ``speedup_vs_fused``, and the host-sync counters —
``pulls_per_round`` is the acceptance metric (the while_loop fixpoint pulls
once per *phase exit*, so deep-chain TC must sit well under one pull per
round), with ``dist_fixpoint_pulls`` / ``dist_fixpoint_iters`` splitting
out how much of the run stayed on-device.
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
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # the caller chose the CPU: simulate the shards as host devices
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   "%(ndev)d")
    import sys, json, time
    sys.path.insert(0, %(src)r)
    import jax
    from repro import compile_cache
    compile_cache.enable()
    from repro.data.kb_sources import (TC, LUBM_L, lubm_facts,
                                       tc_chain_facts, tc_random_facts)
    from repro.engine import ops, plan
    from repro.engine.distributed import materialize_distributed
    from repro.engine.materialize import EngineKB, materialize
    from repro.launch.mesh import make_data_mesh

    if len(jax.devices()) < %(ndev)d:
        print("RESULT []")      # fewer real devices than this shard count
        sys.exit(0)
    mesh = make_data_mesh(%(ndev)d)

    smoke = %(smoke)r
    scens = [
        ("tc_chain", TC, tc_chain_facts(48 if smoke else 128)),
        ("tc_rand", TC, tc_random_facts(*((200, 600) if smoke
                                          else (500, 1500)))),
    ]
    if not smoke:  # the rule-heavy scenario: cold compiles dominate, so
        scens.append(  # it rides only the full table, not the CI smoke
            ("LUBM-L", LUBM_L, lubm_facts(n_univ=2, scale=2)))

    def steady(P, B, run, max_warm=5):
        # warm until no planned capacity moved on the last run: the timed
        # pass then hits only cached programs at converged buffer sizes
        prev = None
        for _ in range(max_warm):
            kb = EngineKB(P, B)
            run(kb)
            snap = sorted((str(k), v) for k, v in plan._CAP_MEMO.items())
            if snap == prev:
                break
            prev = snap
        ops.HOST_SYNC_STATS.reset()
        kb = EngineKB(P, B)
        t0 = time.perf_counter()
        st = run(kb)
        return time.perf_counter() - t0, st, kb

    out = []
    for name, P, B in scens:
        os.environ["REPRO_FUSED"] = "1"
        t_f, st_f, kb_f = steady(P, B, lambda kb: materialize(kb, mode="tg"))
        del os.environ["REPRO_FUSED"]
        fused = {"name": name, "seconds": t_f, "facts": kb_f.num_facts(),
                 "derived": st_f.derived, "rounds": st_f.rounds,
                 "fused_pulls": ops.HOST_SYNC_STATS.fused_pulls}
        t_d, st, kb = steady(
            P, B, lambda kb: materialize_distributed(kb, mode="tg",
                                                     mesh=mesh))
        s = ops.HOST_SYNC_STATS
        out.append({
            "name": name, "seconds": t_d, "ndev": st.extra["ndev"],
            "derived": st.derived, "facts": kb.num_facts(),
            "rounds": st.rounds, "triggers": st.triggers,
            "facts_ref": kb_f.num_facts(),
            "parity": int(kb.num_facts() == kb_f.num_facts()),
            "dist_pulls": s.dist_pulls, "dist_retries": s.dist_retries,
            "dist_fixpoint_pulls": s.dist_fixpoint_pulls,
            "dist_fixpoint_iters": s.dist_fixpoint_iters,
            "fused": fused})
    print("RESULT " + json.dumps(out))
""")


def run(smoke: bool = False):
    scales = (1, 2) if smoke else (1, 2, 4, 8)
    for ndev in scales:
        script = _SCRIPT % {"ndev": ndev, "src": _SRC, "smoke": smoke}
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=3600)
        if r.returncode != 0:
            raise RuntimeError(f"dist bench subprocess ndev={ndev} failed:\n"
                               + r.stderr[-3000:])
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        for rec in json.loads(line[len("RESULT "):]):
            fused = rec["fused"]
            if ndev == scales[0]:
                emit(f"dist.fused_base.{fused['name']}", fused["seconds"],
                     fused["derived"], facts=fused["facts"],
                     rounds=fused["rounds"],
                     fused_pulls=fused["fused_pulls"])
            emit(f"dist.{rec['name']}.ndev{ndev}", rec["seconds"],
                 rec["derived"],
                 ndev=rec["ndev"], facts=rec["facts"],
                 facts_ref=rec["facts_ref"], parity=rec["parity"],
                 rounds=rec["rounds"], triggers=rec["triggers"],
                 dist_pulls=rec["dist_pulls"],
                 dist_retries=rec["dist_retries"],
                 dist_fixpoint_pulls=rec["dist_fixpoint_pulls"],
                 dist_fixpoint_iters=rec["dist_fixpoint_iters"],
                 pulls_per_round=round(rec["dist_pulls"]
                                       / max(rec["rounds"], 1), 3),
                 speedup_vs_fused=round(fused["seconds"]
                                        / max(rec["seconds"], 1e-9), 3))


if __name__ == "__main__":
    import benchmarks.common  # noqa: F401  (sys.path side effect)
    run(smoke="--smoke" in sys.argv)
