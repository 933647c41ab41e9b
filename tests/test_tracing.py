"""The program's own tracing names: ``tg.*`` host spans around the
executor's steps (one ``tg.pull`` per counted host pull, all inside
``tg.materialize``) and ``tg.*`` named scopes on the cores, carried into the
compiled programs' ``op_name`` metadata."""
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.core.terms import parse_atom, parse_program
from repro.engine import fused, ops
from repro.engine.materialize import EngineKB, materialize

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# round 1 runs as a round program, the linear tail as one fixpoint program
TC = parse_program("""
    e(X, Y) -> T(X, Y)
    T(X, Y) & e(Y, Z) -> T(X, Z)
""")
CORES = ("tg.sort", "tg.join", "tg.probe", "tg.merge", "tg.compact")


def _chain(n):
    return [parse_atom(f"e(v{i}, v{i + 1})") for i in range(n)]


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    f = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(f[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tg."):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, dict(e.stats)))
    return spans


def test_fused_spans_pair_with_the_pull_counters(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_FUSED", "1")
    materialize(EngineKB(TC, _chain(10)))          # compile outside
    before = ops.HOST_SYNC_STATS.snapshot()
    jax.profiler.start_trace(str(tmp_path))
    kb = EngineKB(TC, _chain(10))
    st = materialize(kb)
    jax.profiler.stop_trace()
    after = ops.HOST_SYNC_STATS.snapshot()
    assert st.extra.get("fused") is True
    spans = _host_spans(str(tmp_path))
    names = [n for _, _, n, _ in spans]
    pulls = (after.fused_pulls + after.count_pulls
             - before.fused_pulls - before.count_pulls)
    assert pulls > 0 and names.count("tg.pull") == pulls
    assert {"tg.ingest", "tg.encode", "tg.round", "tg.fixpoint",
            "tg.fold"} <= set(names)
    mat = [s for s in spans if s[2] == "tg.materialize"]
    assert len(mat) == 1 and mat[0][3] == {"executor": "fused"}
    lo, hi = mat[0][:2]

    def within(s, a, b):
        return a <= s[0] and s[1] <= b

    steps = [s for s in spans if s[2] in ("tg.round", "tg.fixpoint")]
    assert steps and all(within(s, lo, hi) and "round" in s[3]
                         for s in steps)
    # the executor's pulls lie inside tg.materialize, the ingest's dedup
    # pulls inside its tg.ingest spans
    pull = [s for s in spans if s[2] == "tg.pull"]
    ingest = [s[:2] for s in spans if s[2] == "tg.ingest"]
    assert sum(within(s, lo, hi) for s in pull) == \
        after.fused_pulls - before.fused_pulls
    outside = [s for s in pull if not within(s, lo, hi)]
    assert len(outside) == after.count_pulls - before.count_pulls
    assert all(any(within(s, a, b) for a, b in ingest) for s in outside)


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def test_round_and_fixpoint_programs_carry_the_core_scopes(monkeypatch):
    monkeypatch.setenv("REPRO_FUSED", "1")
    kb = EngineKB(TC, _chain(10))
    materialize(kb)
    progs = fused.lower_fused_programs(kb)
    assert set(progs) == {"round", "fixpoint"}
    for name, (text, _) in progs.items():
        assert f"jit_tg_{name}" in text.split("\n", 1)[0]
        paths = _op_names(text)
        for scope in CORES:
            assert any(f"/{scope}/" in p for p in paths), (name, scope)
        # the outermost tg. component names an op: merge_core's searches
        # are probes nested in tg.merge
        assert any(re.search(r"/tg\.merge/.*tg\.probe/", p) for p in paths)


def test_host_path_programs_are_named_after_their_core():
    data = np.array([[2, 1], [1, 5], [2, 0]], np.int32)
    text = ops._lexsort_fn(3, 2, False).lower(data).as_text()
    assert "tg_lexsort" in text
    text = ops._dedup_count_fn(3, 2, False).lower(data).compile().as_text()
    assert "jit_tg_dedup_count" in text
    assert any("/tg.compact/" in p for p in _op_names(text))


EXCHANGE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re, sys
    sys.path.insert(0, %r)
    from repro.engine.distributed import DistConfig, lower_distributed_tc
    from repro.launch.mesh import make_data_mesh
    cfg = DistConfig(shard_cap=64, delta_cap=16, bucket_cap=8)
    text = lower_distributed_tc(make_data_mesh(4), cfg).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    print(json.dumps({"head": text.split("\\n", 1)[0],
                      "exchange": any("/tg.exchange/" in p for p in paths),
                      "a2a": any("tg.exchange/all_to_all" in p
                                 for p in paths)}))
""" % SRC)


def test_exchange_carries_its_scope_on_four_devices():
    r = subprocess.run([sys.executable, "-c", EXCHANGE], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert "jit_tg_dist_round" in out["head"]
    assert out["exchange"] and out["a2a"]
