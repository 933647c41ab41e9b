"""The trace reduction on a small synthetic profiler trace."""
import os

import pytest

from bench import trace_reduce

# device 0: sort.1 [1, 3) us, fusion.2 [6, 7) us, fusion.2 [8, 9) us;
# device 1: sort.1 [1, 5) us.  Host: ingest [1, 5) us, materialize
# [5, 11) us on the python thread, with the engine's own events inside.
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 11000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%sort.1 = s32[8,2]{1,0} sort(s32[8,2]{1,0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = s32[8]{0} fusion(%a, %b), kind=kLoop" } }
  event_metadata { key: 9 value { id: 9 name: "jit_fn" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "sort.1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "other thread" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.ingest" } }
  event_metadata { key: 2 value { id: 2 name: "bench.materialize" } }
  event_metadata { key: 3 value { id: 3 name: "Dictionary.encode" } }
  event_metadata { key: 4 value { id: 4 name: "device_get" } }
  event_metadata { key: 5 value { id: 5 name: "unrelated" } }
}
'''


@pytest.fixture
def trace_dir(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(tmp_path)


def test_busy_idle_and_breakdown(trace_dir):
    r = trace_reduce.reduce_dir(trace_dir)
    assert r["window_s"] == pytest.approx(10e-6)
    # device 0 busy 4 us, device 1 busy 4 us: the mean over devices
    assert r["busy_s"] == pytest.approx(4e-6)
    assert r["device_ops"] == [["sort.1", pytest.approx(3e-6)],
                               ["fusion.2", pytest.approx(1e-6)]]
    # device 0's gaps in the window: [3, 6), [9, 11), [7, 8) us
    assert r["idle_gaps"] == [
        ["bench.ingest: Dictionary.encode", pytest.approx(3e-6)],
        ["bench.materialize", pytest.approx(2e-6)],
        ["bench.materialize: device_get", pytest.approx(1e-6)]]


def test_no_device_ops_reads_nothing(tmp_path):
    from jax.profiler import ProfileData
    host_only = XSPACE[XSPACE.index("planes {\n  id: 3"):]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(host_only))
    assert trace_reduce.reduce_dir(str(tmp_path)) is None
    assert trace_reduce.reduce_dir(str(tmp_path / "none")) is None


def test_a_recorded_cpu_trace_has_the_host_spans(tmp_path):
    """A real trace from this JAX: the benchmark's spans are on the host
    plane, where the reduction looks for them."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.ingest"):
        jnp.sort(jnp.arange(1000)[::-1]).block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    import glob
    f = glob.glob(os.path.join(tmp_path, "plugins/profile/*/*.xplane.pb"))
    planes = ProfileData.from_file(f[0]).planes
    names = {e.name for p in planes if p.name == trace_reduce.HOST_PLANE
             for line in p.lines for e in line.events}
    assert "bench.ingest" in names
