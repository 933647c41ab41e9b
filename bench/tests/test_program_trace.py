"""The reduction of the program's own steps on small synthetic traces."""
from types import SimpleNamespace as NS

import pytest

from bench import program_trace

US = 1000       # ns


def ev(name, a, b, tf_op=None):
    return NS(name=name, start_ns=a * US, duration_ns=(b - a) * US,
              stats={"tf_op": tf_op} if tf_op else {})


def planes(host, ops):
    return [NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
            NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])]


# two passes, the window [0, 800) us
HOST = [ev("bench.ingest", 0, 100), ev("bench.materialize", 100, 400),
        ev("bench.ingest", 400, 500), ev("bench.materialize", 500, 800),
        ev("tg.encode", 10, 40),
        ev("tg.materialize", 100, 400), ev("tg.round", 110, 200),
        ev("tg.pull", 180, 200), ev("tg.fold", 300, 350),
        ev("tg.materialize", 500, 800), ev("tg.pull", 700, 750)]
FIX = "jit(tg_fixpoint)/while/body/"
OPS = [
    # a fixpoint while (no path of its own) over a probe whose sort is
    # probe work, a merge op, and a copy with no path
    ev("while.1", 120, 300),
    ev("sort.2", 130, 170, FIX + "tg.probe/tg.sort/sort:"),
    ev("add.3", 170, 180, FIX + "tg.merge/add:"), ev("copy.4", 200, 210),
    # a search loop: its body's common path puts it, and the copy inside
    # it, under tg.probe
    ev("while.5", 520, 600),
    ev("ds.6", 530, 560, "jit(tg_round)/tg.probe/jit(searchsorted)/while/"
                         "body/dynamic_slice:"),
    ev("add.7", 560, 570, "jit(tg_round)/tg.probe/add:"),
    ev("copy.8", 580, 590),
    ev("fusion.9", 600, 650, "jit(tg_round)/tg.join/gather:")]


def test_self_time_per_scope_adds_up_to_busy_time():
    r = program_trace.reduce_planes(planes(HOST, OPS))
    assert r["passes"] == 2
    ms = {k: v * 2 / 1e-3 for k, v in r["scope_ms"].items()}   # us, 2 passes
    # while.1: 180 - 40 - 10 - 10 = 120 us of its own, unscoped, as is
    # the copy; while.5 keeps 30 us, under tg.probe with all it holds
    assert ms == pytest.approx({"tg.sort": 0, "tg.join": 50, "tg.probe": 120,
                                "tg.merge": 10, "tg.compact": 0,
                                "tg.exchange": 0, "unscoped": 130})
    assert r["busy_ms"] == pytest.approx(310e-3 / 2)
    assert sum(r["scope_ms"].values()) == pytest.approx(r["busy_ms"])


def test_idle_time_by_the_step_it_fell_in():
    r = program_trace.reduce_planes(planes(HOST, OPS))
    # gaps [0, 120), [300, 520), [650, 800) us; the pull at [180, 200)
    # overlaps device work and idles nothing
    assert r["idle_ms"] == pytest.approx({"host": 270e-3 / 2,
                                          "sync": 50e-3 / 2,
                                          "other": 170e-3 / 2})
    assert r["idle_steps_ms"] == pytest.approx({
        "tg.materialize": 180e-3 / 2, "none": 170e-3 / 2,
        "tg.pull": 50e-3 / 2, "tg.fold": 50e-3 / 2, "tg.encode": 30e-3 / 2,
        "tg.round": 10e-3 / 2})
    assert r["encode_ms"] == pytest.approx(30e-3 / 2)
    assert r["pulls"] == 2
    assert r["idle_gaps"][0] == ["none", pytest.approx(220e-6)]


def test_passes_divide_every_time():
    one = HOST[:2] + [e for e in HOST[4:] if e.start_ns < 400 * US]
    r1 = program_trace.reduce_planes(planes(one, OPS[:4]))
    r2 = program_trace.reduce_planes(planes(HOST, OPS[:4]))
    assert r1["passes"] == 1 and r2["passes"] == 2
    assert r1["scope_ms"]["unscoped"] == pytest.approx(
        2 * r2["scope_ms"]["unscoped"])


def test_a_program_without_names_reads_nothing():
    """A program without the tg. names: its trace reads no scope, no idle
    cause and no encode time, and nothing raises."""
    bare = [NS(name=e.name, start_ns=e.start_ns, duration_ns=e.duration_ns,
               stats={}) for e in OPS]
    r = program_trace.reduce_planes(planes(HOST[:4], bare))
    assert r["scope_ms"] is None and r["idle_ms"] is None
    assert r["encode_ms"] is None
    assert program_trace.reduce_planes(planes(HOST, [])) is None


# the same ops as a serialized trace: the op paths are metadata stats, one
# a string, one a reference to a stat metadata's name
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 900000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 100000
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 180000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 500000000 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while()" } }
  event_metadata { key: 2 value { id: 2 name: "%sort.2 = s32[8] sort()"
    stats { metadata_id: 7 str_value: "jit(tg_round)/while/body/tg.sort/sort:" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = s32[8] fusion()"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 9 value { id: 9 name: "jit_tg_round" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(tg_round)/tg.join/gather:" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 700000000 }
    events { metadata_id: 2 offset_ps: 400000000 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.materialize" } }
  event_metadata { key: 2 value { id: 2 name: "tg.pull" } }
}
'''


def test_the_trace_file_carries_the_paths_as_metadata_stats(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    r = program_trace.reduce_dir(str(tmp_path))
    # while [120, 300) us holds the sort [130, 170); the join at [600, 650)
    us = {k: v / 1e-3 for k, v in r["scope_ms"].items() if v}
    assert us == pytest.approx({"tg.sort": 180, "tg.join": 50})
    # idle in the window [100, 800): the pull [400, 500) is sync
    assert r["idle_ms"]["sync"] == pytest.approx(0.1)
    assert r["idle_ms"]["other"] == pytest.approx(0.37)
    assert program_trace.reduce_dir(str(tmp_path / "none")) is None
