"""Vectorized materialization executors over dictionary-encoded relations.

Modes
-----
* ``seminaive``  — the chase baseline (SNE, per-rule redundancy filtering à la
  VLog: derived facts are deduped against the store right after each rule).
* ``tg``         — TG-guided execution (GLog): per-round nodes are (rule,
  delta-position) groups — the engine-level coalescing of Def. 9 combination
  nodes — executed over *parent* instances only, with the Def. 23 antijoin
  pre-restriction and redundancy filtering once per round.
* ``tg_linear``  — reasoning over a precomputed instance-independent TG
  (tglinear/minLinear) for linear programs, with either deferred collective
  cleaning ("w/ cleaning") or none ("w/o cleaning", counts redundant
  derivations like Table 8a).

Trigger counts = total body instantiations (join output rows / filtered
linear-scan rows) — the paper's hardware-independent work metric.

With ``REPRO_FUSED=1``, the ``tg``/``tg_noopt`` modes route through the
fused round executor (``repro.engine.fused``): whole rounds compile to one
XLA program, and linear-tail fixpoints run inside ``lax.while_loop``.
Programs outside the fused fragment (existentials, disconnected bodies)
fall back to the two-phase executor below; results are identical either
way (gated by ``tests/test_differential.py``).

With ``backend="dist"`` (or ``REPRO_DIST=1``), the same rule plans run on
the sharded shard_map executor (``repro.engine.distributed``): facts
hash-partitioned across local devices, exchanges at the join / absorb
boundaries, one host pull per round.  Same fragment, same fallback, same
differential gate.
"""
from __future__ import annotations

import itertools
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.terms import Atom, Program, Rule, Var, is_var
from repro.engine import ops, recovery
from repro.engine.dictionary import Dictionary
from repro.engine.relation import Relation, lex_order


# ---------------------------------------------------------------------------
# KB container
# ---------------------------------------------------------------------------
class EngineKB:
    def __init__(self, program: Program, base_facts, dtype=None):
        """``dtype``: store dtype for this KB's dictionary ids and relation
        columns (default: the process ``REPRO_STORE_DTYPE``)."""
        self.program = program.normalize()
        self.dict = Dictionary(id_dtype=dtype)
        rows = defaultdict(list)
        self.arities = dict(self.program.arities)
        for f in base_facts:
            rows[f.pred].append(f.args)
            self.arities.setdefault(f.pred, f.arity)
        self.rels: Dict[str, Relation] = {}
        # the base (extensional) facts, tracked separately from the derived
        # closure: incremental deletion (DRed) must know which facts exist by
        # fiat — they are never over-deleted away unless explicitly retracted
        self.base: Dict[str, Relation] = {}
        for p, ar in self.arities.items():
            if p in rows:
                with TraceAnnotation("tg.ingest", pred=p):
                    rel = Relation.from_numpy(self._encode_block(rows[p], ar))
                    # set semantics hold on every path: duplicate base facts
                    # are collapsed regardless of REPRO_SORTED_STORE, so fact
                    # counts and trigger stats agree across flag settings.
                    # (With the sorted store this doubles as the store
                    # invariant: every store relation is lexsorted, so
                    # per-round dedup/antijoin skip their sort pass and
                    # unions become incremental merges.)
                    rel = ops.dedup(rel)
                self.rels[p] = rel
            else:
                self.rels[p] = Relation.empty(max(ar, 1),
                                              dtype=self.dict.id_dtype)
            self.base[p] = self.rels[p]

    def _encode_block(self, fact_args, ar: int) -> np.ndarray:
        """Vectorized encoding of a list of same-arity argument tuples
        (one ``np.unique`` pass via ``Dictionary.encode_columns``); falls
        back to the per-term loop for unorderable mixed terms (Nulls,
        int/str mixes)."""
        n = len(fact_args)
        if n == 0 or ar == 0:
            return np.zeros((n, ar), self.dict.id_dtype)
        with TraceAnnotation("tg.encode"):
            try:
                return self.dict.encode_columns(
                    np.array(fact_args, dtype=object))
            except TypeError:
                enc = [self.dict.encode_many(args) for args in fact_args]
                return np.asarray(enc, self.dict.id_dtype).reshape(n, ar)

    # -- streamed ingest ----------------------------------------------------
    def ingest_rows(self, pred: str, rows: np.ndarray) -> None:
        """Fold one chunk of base rows for ``pred`` into the store: encode
        the (n, ar) term/ndarray block in one vectorized pass, dedup it,
        antijoin against what the store already holds, and merge the fresh
        rows in with the incremental sorted merge.  Chunked callers never
        hold more than one decoded chunk in memory — the store only ever
        grows by sorted merges.

        Each chunk is ATOMIC: the merged store is staged while the old
        relation stays referenced, and the dictionary's interning growth is
        marked first and rolled back if anything in the chunk fails to
        encode or merge — a malformed chunk raises and leaves both the
        dictionary and the store exactly as they were."""
        with TraceAnnotation("tg.ingest", pred=pred):
            self._ingest_chunk(pred, rows)

    def _ingest_chunk(self, pred: str, rows) -> None:
        rows = np.asarray(rows) if not isinstance(rows, np.ndarray) else rows
        if rows.ndim == 1:
            rows = rows.reshape(-1, 1)
        known = self.arities.get(pred)
        if known is not None and len(rows) and rows.shape[1] != known:
            raise ValueError(
                f"ingest chunk for {pred!r} has arity {rows.shape[1]}, "
                f"store expects {known}")
        token = self.dict.mark()
        try:
            enc = self.dict.encode_columns(rows)
            n, ar = enc.shape
            store = self.rels.get(pred)
            if store is None:
                store = Relation.empty(max(ar, 1),
                                       dtype=self.dict.id_dtype)
            staged = store
            if n:
                rel = ops.dedup(Relation.from_numpy(enc))
                if store.count == 0:
                    staged = rel
                else:
                    fresh = ops.antijoin(rel, store)
                    if fresh.count:
                        staged = ops.merge_union(store, fresh)
        except Exception:
            self.dict.rollback(token)
            raise
        # commit point: dictionary growth and the store swap land together
        self.arities.setdefault(pred, ar)
        self.rels[pred] = staged
        self.base[pred] = staged

    @classmethod
    def from_stream(cls, program: Program, chunks, dtype=None) -> "EngineKB":
        """Build a KB from an iterable of ``(pred, (n, ar) ndarray)`` chunks
        (e.g. the ``*_chunks`` generators in ``repro.data.kb_sources``).
        Equivalent to ``EngineKB(program, atoms)`` over the concatenated
        chunks, but peak memory is one chunk plus the padded store — the
        10^8-fact ingest path."""
        kb = cls(program, (), dtype=dtype)
        for pred, rows in chunks:
            kb.ingest_rows(pred, rows)
        return kb

    @classmethod
    def from_arrays(cls, program: Program, tables, dtype=None) -> "EngineKB":
        """Build a KB from ``{pred: (n, ar) ndarray}`` (or an iterable of
        pairs) of already-materialized term arrays."""
        items = tables.items() if hasattr(tables, "items") else tables
        return cls.from_stream(program, items, dtype=dtype)

    def materialize_delta(self, insertions=(), deletions=(), **kw):
        """Incrementally maintain an already-materialized store: see
        :func:`repro.engine.incremental.materialize_delta`."""
        from repro.engine.incremental import materialize_delta
        return materialize_delta(self, insertions=insertions,
                                 deletions=deletions, **kw)

    def insert_facts(self, facts, **kw):
        return self.materialize_delta(insertions=facts, **kw)

    def delete_facts(self, facts, **kw):
        return self.materialize_delta(deletions=facts, **kw)

    def decode_facts(self):
        out = set()
        for p, rel in self.rels.items():
            ar = self.arities[p]
            for row in rel.np_rows():
                out.add(Atom(p, tuple(self.dict.decode(int(x))
                                      for x in row[:ar])))
        return out

    def num_facts(self):
        return sum(r.count for r in self.rels.values())


# ---------------------------------------------------------------------------
# rule plan execution
# ---------------------------------------------------------------------------
def _atom_filters(atom: Atom, dic: Dictionary):
    """(eq_pairs, const_pairs, var->col) for a single atom scan."""
    eq, consts, var_col = [], [], {}
    for i, t in enumerate(atom.args):
        if is_var(t):
            if t in var_col:
                eq.append((var_col[t], i))
            else:
                var_col[t] = i
        else:
            consts.append((i, dic.encode(t)))
    return tuple(eq), tuple(consts), var_col


def execute_rule(kb: EngineKB, rule: Rule, inputs: List[Relation],
                 prefilter: Optional[Relation] = None,
                 prefilter_mode: str = "anti"):
    """Evaluate the body over per-atom input relations.  Returns
    (head_rel (n, head_arity) possibly with PAD skolem marker cols,
     triggers).

    ``prefilter``: Def. 23 — a relation of already-derived head tuples; if
    some body atom's variables cover the head variables, that atom's input is
    antijoined against it before the join (restricting instantiations).
    ``prefilter_mode="semi"`` inverts the restriction (keep only rows whose
    projected head tuple IS in ``prefilter``) — deletion propagation walks
    rule bodies restricted to heads that exist in the store / over-deleted
    set, the mirror image of the insertion-side redundancy filter."""
    dic = kb.dict
    triggers = 0

    # Def. 23 pre-restriction: if some body atom's columns determine the full
    # head tuple, antijoin that atom's input against the derived head facts.
    pre_j = None
    if prefilter is not None and prefilter.count > 0:
        for j, a in enumerate(rule.body):
            _, _, vc = _atom_filters(a, dic)
            if rule.head.args and all(is_var(t) and t in vc
                                      for t in rule.head.args):
                pre_j = (j, tuple(vc[t] for t in rule.head.args))
                break

    cur = None
    var_col: Dict[Var, int] = {}
    for j, atom in enumerate(rule.body):
        eq, consts, vc = _atom_filters(atom, dic)
        rel = ops.filter_rows(inputs[j], eq, consts)
        if pre_j is not None and pre_j[0] == j:
            rel = (ops.semijoin(rel, prefilter, cols=pre_j[1])
                   if prefilter_mode == "semi"
                   else ops.antijoin(rel, prefilter, cols=pre_j[1]))
        if cur is None:
            cur = rel
            var_col = dict(vc)
            continue
        shared = [v for v in vc if v in var_col]
        if not shared:
            joined, m = ops.cross(cur, rel)
            eq2 = []
        else:
            v0 = shared[0]
            joined, m = ops.sm_join(cur, rel, var_col[v0], vc[v0])
            # post-join equality for remaining shared vars
            eq2 = [(var_col[v], cur.arity + vc[v]) for v in shared[1:]]
        if eq2:
            joined = ops.filter_rows(joined, tuple(eq2), ())
        new_var_col = dict(var_col)
        for v, c in vc.items():
            if v not in new_var_col:
                new_var_col[v] = cur.arity + c
        var_col = new_var_col
        cur = joined
    triggers = cur.count

    # head projection
    exvars = rule.existentials
    if not exvars:
        spec = []
        for t in rule.head.args:
            spec.append(var_col[t] if is_var(t) else None)
        cols = [c for c in spec if c is not None]
        head = ops.project(cur, tuple(c if c is not None else 0
                                      for c in spec))
        if any(c is None for c in spec):
            data = np.array(head.data)   # writable copy (np.asarray views
            # jax buffers read-only)
            for i, (t, c) in enumerate(zip(rule.head.args, spec)):
                if c is None:
                    data[:head.count, i] = dic.encode(t)
            head = Relation.from_numpy(data[:head.count])
        return head, triggers

    # skolem existentials (host-side vectorized)
    frontier = [t for t in rule.head.args if is_var(t) and t in var_col]
    fr_cols = [var_col[t] for t in frontier]
    rows = np.asarray(ops.project(cur, tuple(fr_cols or (0,))).data[:cur.count])
    out = np.zeros((cur.count, len(rule.head.args)), dic.id_dtype)
    fcol = {t: i for i, t in enumerate(frontier)}
    # skolem ids are a function of the frontier tuple, so dictionary lookups
    # only need to run once per DISTINCT frontier row, not once per trigger
    if frontier and cur.count:
        uniq, inv = np.unique(rows[:, :len(frontier)], axis=0,
                              return_inverse=True)
        ftuples = [tuple(int(x) for x in u) for u in uniq]
    else:
        uniq = np.zeros((1 if cur.count else 0, 0), np.int32)
        inv = np.zeros(cur.count, np.intp)
        ftuples = [()] * len(uniq)
    for i, t in enumerate(rule.head.args):
        if is_var(t) and t in fcol:
            out[:, i] = rows[:, fcol[t]]
        elif is_var(t):  # existential
            ids = np.fromiter((dic.skolem((rule.name, t.name, ft))
                               for ft in ftuples), dic.id_dtype,
                              len(ftuples))
            out[:, i] = ids[inv]
        else:
            out[:, i] = dic.encode(t)
    return Relation.from_numpy(out), triggers


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------
@dataclass
class MatStats:
    rounds: int = 0
    triggers: int = 0
    derived: int = 0
    mode: str = ""
    extra: dict = field(default_factory=dict)


def materialize(kb: EngineKB, mode: str = "tg", max_rounds: int = 10_000,
                tg_eg=None, cleaning: bool = True,
                backend: Optional[str] = None) -> MatStats:
    """mode: seminaive (VLog-like, per-rule filtering) | tg_noopt (TG round-
    level filtering) | tg (tg_noopt + Def. 23 prefilter) | tg_linear.

    backend: None (env-driven: ``REPRO_DIST=1`` selects "dist") | "dist"
    (sharded shard_map executor over every local device) | "local".  The
    distributed backend covers the plannable fragment of ``tg``/``tg_noopt``
    (no existentials, connected bodies); anything else falls back to the
    fused / two-phase executors below.

    The call runs inside a ``tg.materialize`` host span whose ``executor``
    names the executor that finished it."""
    with TraceAnnotation("tg.materialize") as span:
        st = _materialize(kb, mode, max_rounds, tg_eg, cleaning, backend)
        span.set_metadata(executor="dist" if st.extra.get("dist") else
                          "fused" if st.extra.get("fused") else "two-phase")
    return st


def _materialize(kb, mode, max_rounds, tg_eg, cleaning, backend):
    if mode == "tg_linear":
        return _materialize_tg_linear(kb, tg_eg, cleaning)
    assert mode in ("seminaive", "tg", "tg_noopt")
    if backend is None and ops.dist_enabled():
        backend = "dist"
    if backend == "dist" and mode in ("tg", "tg_noopt"):
        from repro.engine.distributed import materialize_distributed
        st = materialize_distributed(kb, mode=mode, max_rounds=max_rounds)
        if st is not None:  # None: outside the plannable fragment, fall back
            return st
    if mode in ("tg", "tg_noopt") and ops.fused_enabled():
        from repro.engine.fused import materialize_fused
        st = materialize_fused(kb, mode=mode, max_rounds=max_rounds)
        if st is not None:      # None: outside the fused fragment, fall back
            return st
    per_rule = mode == "seminaive"
    st = MatStats(mode=mode)
    program = kb.program
    deltas: Dict[str, Relation] = {}

    ck = recovery.EngineCheckpointer(kb, mode, "two-phase")
    resume = ck.maybe_resume(st)
    if resume is not None:
        st.extra["resumed"] = True
        for p, rows in resume.items():
            deltas[p] = Relation.from_numpy(
                rows, sorted_by=lex_order(rows.shape[1]),
                dtype=kb.dict.id_dtype)
    else:
        # round 1: extensional rules over B
        derived_round = defaultdict(list)
        for rule in program.extensional_rules():
            inputs = [kb.rels[a.pred] for a in rule.body]
            head, trg = execute_rule(kb, rule, inputs)
            st.triggers += trg
            if per_rule:
                _absorb(kb, st, rule.head.pred, head, deltas)
            elif head.count:
                derived_round[rule.head.pred].append(head)
        st.rounds = 1
        if not per_rule:
            for pred, rels in derived_round.items():
                acc = None
                for r in rels:
                    acc = r if acc is None else ops.union(acc, r,
                                                          dedupe=False)
                _absorb(kb, st, pred, acc, deltas)
        ck.boundary(st, lambda: _host_state(kb, deltas))

    _fixpoint_rounds(kb, st, deltas, mode, max_rounds,
                     per_rule=per_rule, ck=ck)
    return st


def _absorb(kb, st, pred, rel, collector):
    """Dedup + antijoin vs store, merge-append, record delta.

    With the sorted store the delta comes out of ``dedup`` lexsorted, the
    antijoin probes the already-sorted store (no sort pass), and the
    surviving rows — disjoint from the store by construction — are folded
    in with an incremental merge instead of concat + resort."""
    if rel is None or rel.count == 0:
        return
    rel = ops.dedup(rel)
    fresh = ops.antijoin(rel, kb.rels[pred])
    if fresh.count == 0:
        return
    if ops.sorted_store_enabled():
        kb.rels[pred] = ops.merge_union(kb.rels[pred], fresh)
    else:
        kb.rels[pred] = ops.union(kb.rels[pred], fresh, dedupe=False)
    st.derived += fresh.count
    if pred in collector:
        # prior deltas for pred are already in the store, so ``fresh`` is
        # disjoint from them too and the merge path applies
        if ops.sorted_store_enabled():
            collector[pred] = ops.merge_union(collector[pred], fresh)
        else:
            collector[pred] = ops.union(collector[pred], fresh,
                                        dedupe=True)
    else:
        collector[pred] = fresh


def _host_state(kb, deltas):
    """Single-shard checkpoint payload for the two-phase executor: trimmed
    lexsorted host rows for every store / live delta / base relation."""
    def rows_of(rel):
        rows = np.asarray(rel.np_rows())
        if len(rows) and not rel.is_lexsorted:
            rows = rows[np.lexsort(rows.T[::-1])]
        return rows
    payload = {}
    for p, rel in kb.rels.items():
        payload[f"store__{p}"] = rows_of(rel)
    for p, rel in deltas.items():
        if rel.count:
            payload[f"delta__{p}"] = rows_of(rel)
    for p, rel in kb.base.items():
        payload[f"base__{p}"] = rows_of(rel)
    return [payload]


def _fixpoint_rounds(kb, st, deltas, mode, max_rounds,
                     per_rule: bool = False, ck=None):
    """Semi-naive fixpoint rounds of the two-phase executor, continuing
    from ``st.rounds`` with the given live ``deltas`` (pred -> Relation).

    Shared by three callers: ``materialize()``'s two-phase path after its
    round 1, a checkpoint resume (seeded with the restored deltas), and
    the fused / distributed drivers' CapacityError SPILL — their last-good
    stores are already in ``kb.rels``, so finishing here degrades
    throughput but never correctness.  With ``ck`` set, each committed
    round is a checkpoint boundary."""
    program = kb.program
    int_rules = list(program.intensional_rules())
    ext_rules = list(program.extensional_rules())

    while deltas and st.rounds < max_rounds:
        derived_round = defaultdict(list)
        new_deltas: Dict[str, Relation] = {}
        # spilled / incremental seeds may sit on EDB predicates, so
        # extensional rules with a live body atom join the round (for a
        # from-scratch run deltas only ever hold derived predicates and
        # this set is empty — the loop is then the classic SNE round)
        live_ext = [r for r in ext_rules
                    if any(a.pred in deltas for a in r.body)]
        for rule in int_rules + live_ext:
            prefilter = (kb.rels.get(rule.head.pred)
                         if mode == "tg" else None)
            for j, atom in enumerate(rule.body):
                if atom.pred not in deltas:
                    continue
                inputs = []
                for i, a in enumerate(rule.body):
                    inputs.append(deltas[atom.pred] if i == j
                                  else kb.rels[a.pred])
                head, trg = execute_rule(kb, rule, inputs,
                                         prefilter=prefilter)
                st.triggers += trg
                if per_rule:
                    _absorb(kb, st, rule.head.pred, head, new_deltas)
                elif head.count:
                    derived_round[rule.head.pred].append(head)
        st.rounds += 1
        if not per_rule:
            for pred, rels in derived_round.items():
                acc = None
                for r in rels:
                    acc = r if acc is None else ops.union(acc, r,
                                                          dedupe=False)
                _absorb(kb, st, pred, acc, new_deltas)
        deltas = new_deltas
        if ck is not None:
            ck.boundary(st, lambda: _host_state(kb, deltas))
    if ck is not None:
        ck.final(st, lambda: _host_state(kb, deltas))
    return st


def _materialize_tg_linear(kb: EngineKB, eg, cleaning: bool) -> MatStats:
    """Reason over an instance-independent TG (Def. 5) for linear programs."""
    assert eg is not None
    st = MatStats(mode=f"tg_linear[{'w' if cleaning else 'wo'}-cleaning]")
    node_rel: Dict[int, Relation] = {}
    for v in eg.topo_order():
        rule = eg.rule_of[v]
        ps = eg.parents(v)
        src = node_rel[ps[0]] if ps else kb.rels[rule.body[0].pred]
        head, trg = execute_rule(kb, rule, [src])
        st.triggers += trg
        node_rel[v] = head
    st.rounds = eg.graph_depth() + 1
    # union node instances into the store
    by_pred = defaultdict(list)
    for v, rel in node_rel.items():
        by_pred[eg.rule_of[v].head.pred].append(rel)
    for pred, rels in by_pred.items():
        acc = None
        for r in rels:
            acc = r if acc is None else ops.union(acc, r, dedupe=False)
        if acc is None:
            continue
        if cleaning:
            acc = ops.dedup(acc)
            acc = ops.antijoin(acc, kb.rels[pred])
        st.derived += acc.count
        if cleaning and ops.sorted_store_enabled():
            kb.rels[pred] = ops.merge_union(kb.rels[pred], acc)
        else:
            kb.rels[pred] = ops.union(kb.rels[pred], acc, dedupe=not cleaning)
    return st
