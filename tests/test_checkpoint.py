"""Checkpoint manager: save/restore round-trip, async save, resume, elastic
restore, preemption-driven exit, and the KB data pipeline state."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.data.pipeline import KBLinearizer, SyntheticTokens
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.layers import MeshCtx
from repro.train import optimizer as OPT
from repro.train.checkpoint import CheckpointManager
from repro.train.train_loop import train


def _mcx():
    mesh = make_host_mesh()
    return MeshCtx(mesh=mesh, dp=("data",), tp="model")


def test_save_restore_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32)}}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, tree, extra={"step": 7}, blocking=True)
    assert mgr.latest_step() == 7
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    restored, extra = mgr.restore(abstract)
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, blocking=True)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3, 4]


def test_train_resume(tmp_path):
    cfg = get_smoke_config("stablelm_12b")
    mdl = M.build(cfg, _mcx())
    data = SyntheticTokens(cfg.vocab_size, batch=4, seq=32, seed=1)
    p1, o1, losses1 = train(mdl, data, steps=6, ckpt_dir=str(tmp_path),
                            ckpt_every=3, log_every=100, log=lambda *a: None)
    # second run resumes from step 6 checkpoint and continues to 8
    data2 = SyntheticTokens(cfg.vocab_size, batch=4, seq=32, seed=1)
    p2, o2, losses2 = train(mdl, data2, steps=8, ckpt_dir=str(tmp_path),
                            ckpt_every=3, log_every=100, log=lambda *a: None)
    assert data2.step >= 2   # only ran the remaining steps (6..8)
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == 8


def test_kb_linearizer_stream():
    from repro.core.terms import parse_atom, parse_program
    from repro.engine.materialize import EngineKB, materialize
    P = parse_program("""
        e(X, Y) -> T(X, Y)
        T(X, Y) & e(Y, Z) -> T(X, Z)
    """)
    B = [parse_atom(f"e(v{i}, v{i+1})") for i in range(6)]
    kb = EngineKB(P, B)
    materialize(kb, mode="tg")
    lin = KBLinearizer(kb, batch=2, seq=16)
    b1 = lin.next()
    assert b1["tokens"].shape == (2, 16)
    assert b1["tokens"].max() < lin.vocab_size
    st = lin.state()
    b2 = lin.next()
    lin.restore(st)
    b2_again = lin.next()
    np.testing.assert_array_equal(b2["tokens"], b2_again["tokens"])


def test_elastic_restore_changes_sharding(tmp_path):
    """Checkpoint written replicated, restored with an explicit sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mcx = _mcx()
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, tree, blocking=True)
    abstract = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    sh = {"w": NamedSharding(mcx.mesh, P("data", None))}
    restored, _ = mgr.restore(abstract, sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(16.0).reshape(4, 4))
