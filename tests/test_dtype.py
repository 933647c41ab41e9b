"""Narrow-dtype store: executor parity across dtypes, key-packing parity
vs numpy oracles, ingest-time overflow contracts, and the streamed
(chunked-ndarray) ingest path."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.terms import parse_atom, parse_program
from repro.engine import ops
from repro.engine.dictionary import Dictionary
from repro.engine.materialize import EngineKB, materialize
from repro.engine.relation import Relation, id_range, pad_value, store_dtype

TC = parse_program("""
    e(X, Y) -> T(X, Y)
    T(X, Y) & e(Y, Z) -> T(X, Z)
""")


def _chain(n, extra=0, seed=0):
    rng = np.random.default_rng(seed)
    edges = [(i, i + 1) for i in range(n)]
    edges += [tuple(e) for e in rng.integers(0, n, (extra, 2))]
    return [parse_atom(f"e(v{a}, v{b})") for a, b in edges]


# ---------------------------------------------------------------------------
# engine parity: int16 == int32 closures across executors / kernel paths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", ["0", "1"])
@pytest.mark.parametrize("pallas", ["0", "1"])
def test_int16_matches_int32_closure(fused, pallas, monkeypatch):
    monkeypatch.setenv("REPRO_FUSED", fused)
    monkeypatch.setenv("REPRO_USE_PALLAS", pallas)
    B = _chain(20, extra=12, seed=5)
    kb32 = EngineKB(TC, B, dtype=np.int32)
    materialize(kb32, mode="tg")
    kb16 = EngineKB(TC, B, dtype=np.int16)
    materialize(kb16, mode="tg")
    assert kb16.rels["T"].dtype == np.dtype(np.int16)
    assert kb32.rels["T"].dtype == np.dtype(np.int32)
    assert kb16.decode_facts() == kb32.decode_facts()


def test_store_dtype_env(monkeypatch):
    monkeypatch.setenv("REPRO_STORE_DTYPE", "int16")
    assert store_dtype() == np.dtype(np.int16)
    kb = EngineKB(TC, _chain(4))
    assert kb.rels["e"].dtype == np.dtype(np.int16)
    monkeypatch.setenv("REPRO_STORE_DTYPE", "int64")
    # int64 stores need an x64-enabled process (see subprocess test below)
    with pytest.raises(RuntimeError):
        store_dtype()


# ---------------------------------------------------------------------------
# packing parity vs numpy oracles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.int16, np.int32])
def test_lexsort_core_matches_np_lexsort(dtype):
    rng = np.random.default_rng(7)
    hi = min(200, id_range(np.dtype(dtype))[1])
    rows = rng.integers(0, hi, (100, 2)).astype(dtype)
    got = np.asarray(ops.lexsort_core(rows))
    ref = rows[np.lexsort(rows.T[::-1])]
    assert got.dtype == rows.dtype
    assert (got == ref).all()


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
def test_pack_rows2_roundtrip_order(dtype):
    """Arity-2 rows have no packed scalar key at any width: they sort by
    both columns, in row-major lexicographic order, including values
    adjacent to the PAD sentinel."""
    dt = np.dtype(dtype)
    hi = id_range(dt)[1]
    pad = hi + 1
    rows = np.array([[0, 0], [0, hi], [hi, 0], [hi, hi], [1, hi - 1],
                     [pad, pad], [hi, pad]], dt)
    import jax.numpy as jnp
    assert ops.scalar_key(jnp.asarray(rows)) is None
    got = np.asarray(ops.lexsort_core(jnp.asarray(rows), pallas=False))
    assert (got == rows[np.lexsort(rows.T[::-1])]).all()


def test_member_mask_pack_vs_binary_search_fallback():
    """int16 rows take the per-column binary search; membership must agree
    with the range search it is built on and with a python set."""
    rng = np.random.default_rng(11)
    hay = np.unique(rng.integers(0, 60, (80, 2)).astype(np.int16), axis=0)
    probe = rng.integers(0, 60, (40, 2)).astype(np.int16)
    import jax.numpy as jnp
    hay_j, probe_j = jnp.asarray(hay), jnp.asarray(probe)
    packed = np.asarray(ops.member_mask_core(probe_j, hay_j))
    lo, hi = ops.lex_range_core(hay_j, probe_j)
    fallback = np.asarray(lo < hi)
    ref = np.array([tuple(r) in {tuple(h) for h in hay} for r in probe])
    assert (packed.astype(bool) == ref).all()
    assert (fallback == ref).all()


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
def test_narrow_ops_check_matches_numpy(dtype):
    """Every row core a narrow store runs (and the int16 pair key the
    chip-side op check probes) agrees with numpy on the default backend."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "narrow_ops_check.py")
    spec = importlib.util.spec_from_file_location("narrow_ops_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    res = mod.check_ops(np.dtype(dtype), n=512, seed=3)
    assert len(res) == (11 if dtype == np.int16 else 7)
    assert all(res.values()), res


@pytest.mark.parametrize("dtype", [np.int16, np.int32])
@pytest.mark.parametrize("n", [0, 1, 3, 7])
def test_dedup_edge_shapes(dtype, n):
    """Empty and non-pow2 row counts through the dtype-generic cores."""
    rng = np.random.default_rng(n)
    hi = min(40, id_range(np.dtype(dtype))[1])
    rows = rng.integers(0, hi, (n, 2)).astype(dtype)
    rel = Relation.from_numpy(rows)
    out = ops.dedup(rel)
    assert out.dtype == np.dtype(dtype)
    assert out.rows_set() == {tuple(r) for r in rows.tolist()}


# ---------------------------------------------------------------------------
# overflow contracts: fail at ingest, never wrap
# ---------------------------------------------------------------------------
def test_relation_narrowing_overflow():
    rows = np.array([[70000, 1]], np.int64)
    with pytest.raises(OverflowError):
        Relation.from_numpy(rows, dtype=np.int16)
    # PAD itself is reserved even when in range
    pad = int(pad_value(np.dtype(np.int16)))
    with pytest.raises(OverflowError):
        Relation.from_numpy(np.array([[pad, 0]], np.int64), dtype=np.int16)


def test_dictionary_overflow_is_atomic():
    d = Dictionary(np.int16)
    with pytest.raises(OverflowError):
        d.encode_columns(np.arange(80000, dtype=np.int64).reshape(-1, 2))
    assert len(d) == 0
    with pytest.raises(OverflowError):
        for i in range(40000):
            d.encode(f"t{i}")


def test_skolem_overflow_int16():
    d = Dictionary(np.int16)
    lo = id_range(np.dtype(np.int16))[0]
    with pytest.raises(OverflowError):
        for i in range(-lo + 1):
            d.skolem(("r", "x", (i,)))


# ---------------------------------------------------------------------------
# int64 store: requires an x64-enabled process end to end
# ---------------------------------------------------------------------------
def test_int64_store_subprocess_parity():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    script = textwrap.dedent(f"""
        import os
        os.environ["JAX_ENABLE_X64"] = "1"
        os.environ["REPRO_STORE_DTYPE"] = "int64"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path.insert(0, {src!r})
        import numpy as np
        from repro.core.terms import parse_atom, parse_program
        from repro.engine.materialize import EngineKB, materialize
        P = parse_program('e(X,Y) -> T(X,Y)\\nT(X,Y) & e(Y,Z) -> T(X,Z)')
        B = [parse_atom(f'e(v{{i}}, v{{i+1}})') for i in range(12)]
        for fused in ("0", "1"):
            os.environ["REPRO_FUSED"] = fused
            kb = EngineKB(P, B)
            materialize(kb, mode="tg")
            assert kb.rels["T"].dtype == np.dtype(np.int64), kb.rels["T"].dtype
            assert kb.rels["T"].count == 12 * 13 // 2, kb.rels["T"].count
        print("OK64")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK64" in r.stdout


# ---------------------------------------------------------------------------
# streamed ingest
# ---------------------------------------------------------------------------
def test_from_stream_matches_atom_ingest():
    from repro.core.terms import Atom
    rng = np.random.default_rng(3)
    edges = rng.integers(0, 30, (200, 2)).astype(np.int32)
    atoms = [Atom("e", (a, b)) for a, b in edges.tolist()]
    kb_atoms = EngineKB(TC, atoms)
    materialize(kb_atoms, mode="tg")
    # overlapping chunks: ingest must dedup against the store
    chunks = [("e", edges[:120]), ("e", edges[80:])]
    kb_stream = EngineKB.from_stream(TC, iter(chunks))
    materialize(kb_stream, mode="tg")
    assert kb_stream.decode_facts() == kb_atoms.decode_facts()


def test_from_arrays_dict_form():
    kb = EngineKB.from_arrays(
        TC, {"e": np.array([[0, 1], [1, 2]], np.int32)})
    materialize(kb, mode="tg")
    assert kb.rels["T"].count == 3


def test_tc_wide_chunks_closure_count():
    from repro.data.kb_sources import tc_wide_chunks, tc_wide_total
    kb = EngineKB.from_stream(TC, tc_wide_chunks(7, chunk_rows=8))
    materialize(kb, mode="tg")
    total = sum(kb.rels[p].count for p in kb.rels if "~" not in p)
    assert total == tc_wide_total(7) == 7 * 14


def test_tc_wide_chunks_overflow():
    from repro.data.kb_sources import tc_wide_chunks
    with pytest.raises(OverflowError):
        next(tc_wide_chunks(50000, dtype=np.int16))


def test_tc_random_facts_uses_store_dtype(monkeypatch):
    from repro.data import kb_sources
    monkeypatch.setenv("REPRO_STORE_DTYPE", "int16")
    facts = kb_sources.tc_random_facts(n_nodes=50, n_edges=100)
    assert all(a.pred == "e" for a in facts)


# ---------------------------------------------------------------------------
# dictionary round-trip property
# ---------------------------------------------------------------------------
def test_encode_columns_roundtrip_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    term = st.one_of(st.integers(-2 ** 40, 2 ** 40),
                     st.text(max_size=6))

    @settings(deadline=None, max_examples=50)
    @given(st.lists(st.tuples(term, term), max_size=40), st.data())
    def check(pairs, data):
        d = Dictionary(np.int32)
        # split the batch at an arbitrary point: interning must be stable
        # across successive batches
        cut = data.draw(st.integers(0, len(pairs)))
        outs = []
        for part in (pairs[:cut], pairs[cut:]):
            if not part:
                continue
            arr = np.array(part, dtype=object)
            outs.append((part, d.encode_columns(arr)))
        for part, ids in outs:
            assert ids.dtype == np.dtype(np.int32)
            for (a, b), (ia, ib) in zip(part, ids.tolist()):
                assert d.decode(ia) == a and d.decode(ib) == b
                assert d.encode(a) == ia and d.encode(b) == ib

    check()


def test_encode_columns_mixed_str_int():
    """A column mixing str and int terms cannot be sorted; it interns term
    by term, each value in its own store, and round-trips."""
    d = Dictionary(np.int32)
    arr = np.array([(0, ""), ("a", 7), (0, "a")], dtype=object)
    ids = d.encode_columns(arr)
    assert ids.dtype == np.dtype(np.int32)
    assert [[d.decode(int(i)) for i in row] for row in ids] == \
        [[0, ""], ["a", 7], [0, "a"]]
    assert ids[0, 0] == ids[2, 0] and ids[1, 0] == ids[2, 1]


def test_encode_many_matches_encode():
    d1, d2 = Dictionary(np.int32), Dictionary(np.int32)
    terms = [f"s{i % 9}" for i in range(100)] + list(range(50)) * 2
    assert d1.encode_many(terms) == [d2.encode(t) for t in terms]


def test_encode_many_tuple_terms():
    # tuples are hashable terms; the bulk path must intern each tuple as
    # ONE term, not splat its elements into separate ids
    d = Dictionary(np.int32)
    terms = [(i % 7, i % 5) for i in range(70)]
    ids = d.encode_many(terms)
    assert len(ids) == len(terms)
    assert [d.decode(i) for i in ids] == terms
    assert d.encode_many(terms) == ids          # stable re-intern
    assert d.encode(terms[3]) == ids[3]          # scalar path agrees


def test_encode_many_ragged_tuples_fall_back():
    # unequal-length tuples are unorderable for np.unique; the bulk path
    # must fall back per-term instead of raising
    d = Dictionary(np.int32)
    terms = [(1, 2), (1, 2, 3)] * 40
    ids = d.encode_many(terms)
    assert [d.decode(i) for i in ids] == terms


def test_encode_columns_uint64_no_wrap():
    # a native uint64 ndarray above int64 max must not astype-wrap into a
    # negative (null-colliding) term; it routes to the generic store
    d = Dictionary(np.int32)
    big = int(np.iinfo(np.uint64).max)
    col = np.array([big, 5, 7], dtype=np.uint64).reshape(-1, 1)
    ids = d.encode_columns(col)
    assert [d.decode(int(i)) for i in ids[:, 0]] == [big, 5, 7]
    assert d.encode(big) == int(ids[0, 0])
    # in-range unsigned input still takes the vectorized int path
    d2 = Dictionary(np.int32)
    ok = np.arange(100, dtype=np.uint64).reshape(-1, 2)
    assert [d2.decode(int(i))
            for i in d2.encode_columns(ok).reshape(-1)] == list(range(100))
