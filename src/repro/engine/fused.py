"""Fused round executor: one XLA program per materialization round.

The two-phase wrappers in ``repro.engine.ops`` pull every data-dependent
count to the host (one blocking sync per primitive call) to pick pow-2
output buckets — on small-delta rounds those host round-trips, not the join
arithmetic, dominate wall time.  This module removes them:

* The **rule-plan IR** (``repro.engine.plan``: ``RulePlan`` /
  ``compile_rule_plan``), its capacity planner (``_Caps``), and the traced
  round pieces (``_exec_rule_traced`` / ``_absorb_traced``) are backend-
  neutral — the distributed executor consumes the same plans.  This module
  stitches them into one jitted, shape-stable program per (rule set,
  capacity plan): body filters, the Def. 23 antijoin pre-restriction, the
  sort-merge join chain, head projection, and the per-predicate absorb
  (dedup + antijoin vs store + incremental sorted merge) all run in a
  single XLA executable.  The only device->host traffic per round is one
  scalar bundle: counts, the trigger total, and an overflow vector
  (``HOST_SYNC_STATS.fused_pulls``).
* A **fused fixpoint driver** runs whole semi-naive/TG rounds this way, and
  once the remaining computation is *linear* — every still-active rule has
  exactly one body atom whose predicate can still change — it finishes the
  entire fixpoint inside one ``lax.while_loop``, with loop-state buffers
  donated to XLA on accelerator backends.

Overflow semantics (mirrors the distributed bucket-exchange contract):
every planned capacity gets an in-program overflow flag (``needed >
planned``).  When any flag fires the round's outputs are discarded, the
host doubles exactly the overflowed capacities, recompiles at the new
buckets, and retries the same round from the inputs it still holds
(``HOST_SYNC_STATS.fused_retries``).  Inside the fixpoint loop an overflow
exits with the *last good* state, so the retry resumes mid-fixpoint — it
never recomputes from scratch.

Eligibility: Datalog rules (no existentials) with connected bodies.
``materialize()`` falls back to the two-phase path for anything else.

Tracing: the programs are jitted as ``tg_round`` and ``tg_fixpoint``, the
names the device trace's ``XLA Modules`` line shows.  On the host, each
round attempt is one ``tg.round`` span and each fixpoint-program entry one
``tg.fixpoint`` span (``jax.profiler.TraceAnnotation``), from argument
preparation to the return of the ``tg.pull`` inside it; the fold of tails
into the stores after a fixpoint exit is ``tg.fold``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.engine import ops, recovery
from repro.engine.plan import (_absorb_traced, _cached_program, _Caps,
                               _exec_rule_traced, _linear_tail,
                               _select_state, CapacityError,
                               compile_rule_plan, program_fingerprint,
                               RetryBudget, RulePlan)
from repro.engine.relation import Relation, lex_order, pad_of

__all__ = ["RulePlan", "compile_rule_plan", "materialize_fused",
           "lower_fused_programs"]


# ---------------------------------------------------------------------------
# compiled round program
# ---------------------------------------------------------------------------
def _round_signature(preds, caps, active, delta_in, use_prefilter, pallas):
    return ("round", preds,
            tuple(caps.store[p] for p in preds),
            tuple((plan.key, jd, tuple(caps.join_cap(plan, i)
                                       for i in range(len(plan.joins))))
                  for plan, jd in active),
            tuple((p, caps.delta_cap(p)) for p in delta_in),
            tuple(sorted((p, caps.delta_cap(p)) for p in
                         {plan.head_pred for plan, _ in active})),
            use_prefilter, pallas)


def _build_round(preds, caps, active, delta_in, use_prefilter, pallas):
    """One materialization round as a single jitted program.

    Inputs: per-pred store blocks (at planner capacities) + counts, plus the
    live delta blocks (at planner delta capacities).  Outputs: new stores /
    counts, new per-derived-pred deltas + counts, the round's trigger total,
    and the overflow vector.  ``ovf_labels`` names each overflow slot so the
    driver can double exactly the right capacity."""
    derived = tuple(sorted({plan.head_pred for plan, _ in active}))
    ovf_labels = []
    for plan, jd in active:
        for i in range(len(plan.joins)):
            ovf_labels.append(("join", (plan.key, i)))
    for pred in derived:
        ovf_labels.append(("delta", pred))
        ovf_labels.append(("store", pred))
    join_caps = {id(plan): tuple(caps.join_cap(plan, i)
                                 for i in range(len(plan.joins)))
                 for plan, _ in active}
    delta_caps = {p: caps.delta_cap(p) for p in derived}

    def tg_round(store_datas, store_counts, delta_datas):
        stores = dict(zip(preds, store_datas))
        counts = dict(zip(preds, store_counts))
        deltas = dict(zip(delta_in, delta_datas))
        triggers = jnp.zeros((), jnp.int32)
        ovfs = []
        heads = {}
        for plan, jd in active:
            inputs = [deltas[bp] if j == jd else stores[bp]
                      for j, bp in enumerate(plan.body_preds)]
            pre_data = stores[plan.head_pred] if use_prefilter else None
            head, trg, jovfs = _exec_rule_traced(plan, inputs, pre_data,
                                                 join_caps[id(plan)], pallas)
            triggers += trg
            ovfs += jovfs
            heads.setdefault(plan.head_pred, []).append(head)
        out_deltas, out_dcounts = [], []
        for pred in derived:
            ns, nc, delta, nf, (od, os_) = _absorb_traced(
                heads[pred],
                lambda rows, p=pred: jnp.logical_not(
                    ops.member_mask_core(rows, stores[p])),
                stores[pred], counts[pred], delta_caps[pred], pallas)
            stores[pred] = ns
            counts[pred] = nc
            out_deltas.append(delta)
            out_dcounts.append(nf)
            ovfs += [od, os_]
        ovf_vec = (jnp.stack(ovfs) if ovfs
                   else jnp.zeros((0,), jnp.bool_))
        return (tuple(stores[p] for p in preds),
                tuple(counts[p] for p in preds),
                tuple(out_deltas), tuple(out_dcounts), triggers, ovf_vec)

    return jax.jit(tg_round), ovf_labels, derived


# ---------------------------------------------------------------------------
# fused fixpoint (lax.while_loop over whole rounds; linear-tail detection
# and the last-good-state select are shared with the distributed fixpoint
# via repro.engine.plan)
# ---------------------------------------------------------------------------
def _fix_signature(s_preds, o_preds, caps, active, use_prefilter, pallas,
                   max_rounds, donate):
    return ("fix", s_preds, o_preds,
            tuple(caps.store[p] for p in s_preds + o_preds),
            tuple(caps.delta_cap(p) for p in s_preds),
            tuple(caps.tail_cap(p) for p in s_preds),
            tuple((plan.key, jd, tuple(caps.join_cap(plan, i)
                                       for i in range(len(plan.joins))))
                  for plan, jd in active),
            use_prefilter, pallas, max_rounds, donate)


def _build_fixpoint(s_preds, o_preds, caps, active, use_prefilter, pallas,
                    max_rounds, donate):
    """The remaining (linear) fixpoint as one ``lax.while_loop`` program.

    Loop state: the deltas of the still-changing predicates plus a small
    sorted *tail* buffer per predicate.  The phase-entry stores are loop
    CONSTANTS — redundancy filtering probes (base store | tail), and each
    round's fresh facts merge into the tail (O(tail) work per iteration,
    not O(store)).  When a tail fills, the loop exits with the last good
    state, the host folds the tail into its store once, and the loop
    re-enters — the fixpoint resumes, never restarts.  Join/delta capacity
    overflows exit the same way and retry after host-side doubling."""
    derived = tuple(sorted({plan.head_pred for plan, _ in active}))
    ovf_labels = []
    for plan, jd in active:
        for i in range(len(plan.joins)):
            ovf_labels.append(("join", (plan.key, i)))
    for pred in derived:
        ovf_labels.append(("delta", pred))
        ovf_labels.append(("tail", pred))
    n_ovf = len(ovf_labels)
    join_caps = {id(plan): tuple(caps.join_cap(plan, i)
                                 for i in range(len(plan.joins)))
                 for plan, _ in active}
    delta_caps = {p: caps.delta_cap(p) for p in s_preds}

    def tg_fixpoint(s_base, w_datas, w_counts, d_datas, d_counts, o_datas,
                    rounds):
        base = dict(zip(s_preds, s_base))
        others = dict(zip(o_preds, o_datas))

        def not_seen(rows, pred, tails, cols=None):
            """keep-mask: rows whose tuple is in neither the phase-entry
            store nor the tail of ``pred``."""
            sel = rows if cols is None else ops.project_core(rows, cols)
            seen = jnp.logical_or(
                ops.member_mask_core(sel, base[pred]),
                ops.member_mask_core(sel, tails[pred]))
            valid = rows[:, 0] != pad_of(rows)
            return jnp.logical_and(valid, jnp.logical_not(seen))

        def body(state):
            w_datas, w_counts, d_datas, d_counts, rounds, trg, drv, _ = state
            tails = dict(zip(s_preds, w_datas))
            wcnt = dict(zip(s_preds, w_counts))
            deltas = dict(zip(s_preds, d_datas))
            stores = dict(others)
            triggers = jnp.zeros((), jnp.int32)
            ovfs = []
            heads = {}
            for plan, jd in active:
                inputs = []
                for j, bp in enumerate(plan.body_preds):
                    # linear tail: the only S-pred body atom is the delta
                    inputs.append(deltas[bp] if j == jd else stores[bp])
                head, t, jovfs = _exec_rule_traced(
                    plan, inputs, None, join_caps[id(plan)], pallas,
                    prefilter=((lambda rows, cols, p=plan.head_pred:
                                not_seen(rows, p, tails, cols))
                               if use_prefilter else None))
                triggers += t
                ovfs += jovfs
                heads.setdefault(plan.head_pred, []).append(head)
            new_w, new_wc, new_deltas, new_dcounts = {}, {}, {}, {}
            for pred in s_preds:
                if pred in heads:
                    nw, nc, delta, nf, (od, ow) = _absorb_traced(
                        heads[pred],
                        lambda rows, p=pred: not_seen(rows, p, tails),
                        tails[pred], wcnt[pred], delta_caps[pred], pallas)
                    new_w[pred], new_wc[pred] = nw, nc
                    new_deltas[pred], new_dcounts[pred] = delta, nf
                    ovfs += [od, ow]
                else:   # in S but not derived by any active rule: drains
                    new_w[pred] = tails[pred]
                    new_wc[pred] = wcnt[pred]
                    new_deltas[pred] = jnp.full_like(deltas[pred],
                                                     pad_of(deltas[pred]))
                    new_dcounts[pred] = jnp.zeros((), jnp.int32)
            ovf_vec = (jnp.stack(ovfs) if ovfs
                       else jnp.zeros((0,), jnp.bool_))
            bad = jnp.any(ovf_vec) if n_ovf else jnp.array(False)

            def keep(old, new):
                return _select_state(bad, old, new)

            return (keep(w_datas, tuple(new_w[p] for p in s_preds)),
                    keep(w_counts, tuple(new_wc[p] for p in s_preds)),
                    keep(d_datas, tuple(new_deltas[p] for p in s_preds)),
                    keep(d_counts, tuple(new_dcounts[p] for p in s_preds)),
                    rounds + jnp.where(bad, 0, 1),
                    trg + jnp.where(bad, 0, triggers),
                    drv + jnp.where(bad, 0,
                                    sum(new_dcounts[p] for p in s_preds)),
                    ovf_vec)

        def cond(state):
            _, _, _, d_counts, rounds, _, _, ovf_vec = state
            live = sum(d_counts) > 0
            ok = jnp.logical_not(jnp.any(ovf_vec)) if n_ovf else True
            return jnp.logical_and(jnp.logical_and(live, ok),
                                   rounds < max_rounds)

        state = (w_datas, w_counts, d_datas, d_counts, rounds,
                 jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                 jnp.zeros((n_ovf,), jnp.bool_))
        return jax.lax.while_loop(cond, body, state)

    # loop-state buffers are donated on accelerator backends (exits return
    # the last-good state, so the donated inputs are never needed again)
    return (jax.jit(tg_fixpoint, donate_argnums=(1, 3) if donate else ()),
            ovf_labels)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def materialize_fused(kb, mode: str = "tg", max_rounds: int = 10_000,
                      initial_deltas=None, spill: bool = True):
    """Fused-program materialization of ``kb``.  Returns MatStats, or None
    when the program is outside the fused fragment (the caller falls back to
    the two-phase executor).

    ``initial_deltas`` (pred -> lexsorted Relation of rows ALREADY absorbed
    into the store) switches the driver to incremental mode: round 1 over the
    extensional rules is skipped and the seeded deltas enter the semi-naive
    loop directly — the entry point behind
    ``repro.engine.incremental.materialize_delta``.  Seeded deltas may live
    on EDB predicates, so the loop considers every rule with a live body
    atom, not just the intensional ones (for from-scratch runs the two sets
    coincide: deltas only ever hold derived predicates).

    Capacity overflows retry under a ``RetryBudget``
    (``REPRO_MAX_RETRIES`` / ``REPRO_MAX_RESIDENT_MB``); when the budget is
    exhausted mid-run the driver writes its last-good state back and
    ``spill``s the remaining rounds to the two-phase executor instead of
    doubling buffers toward OOM (``spill=False`` re-raises the
    ``CapacityError`` — tests use it to observe the diagnostic).

    With ``REPRO_CKPT_DIR`` set, the driver checkpoints at every host
    pull boundary (post-ext round, every host-stepped round, every
    fixpoint exit) and resumes from the newest valid checkpoint —
    including checkpoints written by the other executors."""
    from repro.engine.materialize import MatStats
    program = kb.program
    plans = {}
    for rule in program.rules:
        plan = compile_rule_plan(rule, kb.dict)
        if plan is None:
            return None
        plans[id(rule)] = plan

    preds = tuple(sorted(kb.rels))
    use_prefilter = mode == "tg"
    pallas = ops.use_pallas()
    donate = jax.default_backend() != "cpu"
    st = MatStats(mode=mode)
    st.extra["fused"] = True

    # delta-mode lifecycles belong to the caller: no checkpointing there
    ck = recovery.EngineCheckpointer(kb, mode, "fused",
                                     enabled=initial_deltas is None)
    resume = ck.maybe_resume(st)    # replaces kb.dict / kb.rels on success

    # fused precondition: lexsorted, set-semantic stores
    stores, counts = {}, {}
    for p in preds:
        rel = kb.rels[p]
        if rel.count and not rel.is_lexsorted:
            rel = ops.dedup(rel)
        stores[p], counts[p] = rel.data, rel.count
    fp = program_fingerprint((plans[id(r)].key for r in program.rules),
                             sum(counts.values()))
    caps = _Caps(fp, {p: (stores[p], counts[p]) for p in preds},
                 lean=initial_deltas is not None)
    if ck.caps_state is not None:
        caps.adopt(ck.caps_state)   # converged plan from the checkpoint
    for p in preds:
        stores[p] = ops.fit_rows(stores[p], caps.store[p])

    row_bytes = max((kb.rels[p].dtype.itemsize * kb.arities[p]
                     for p in preds), default=8)
    budget = RetryBudget(caps, row_bytes=row_bytes)

    ext_plans = [plans[id(r)] for r in program.extensional_rules()]
    loop_rules = list(program.rules)
    loop_plans = [plans[id(r)] for r in loop_rules]
    deltas: dict = {}           # pred -> (data at planner delta cap, count)
    progressed = resume is not None

    def state_fn():
        """Host-consistent checkpoint payload (single shard): trimmed
        stores, live deltas, and the base facts."""
        payload = {}
        for p in preds:
            payload[f"store__{p}"] = np.asarray(stores[p])[:counts[p]]
        for p, (d, c) in deltas.items():
            rows = np.asarray(d)[:int(c)]
            payload[f"delta__{p}"] = rows[np.lexsort(rows.T[::-1])]
        for p, rel in kb.base.items():
            payload[f"base__{p}"] = rel.np_rows()
        return [payload]

    def run_round(active, delta_preds, is_ext=False):
        nonlocal stores, counts
        prefilter = use_prefilter and not is_ext   # no Def. 23 in round 1
        while True:
            with TraceAnnotation("tg.round", round=st.rounds):
                sig = _round_signature(preds, caps, active, delta_preds,
                                       prefilter, pallas)
                fn, ovf_labels, derived = _cached_program(
                    sig, lambda: _build_round(preds, caps, active,
                                              delta_preds, prefilter,
                                              pallas))
                out = fn(tuple(stores[p] for p in preds),
                         tuple(jnp.int32(counts[p]) for p in preds),
                         tuple(ops.fit_rows(deltas[p][0], caps.delta_cap(p))
                               for p in delta_preds))
                n_stores, n_counts, n_deltas, n_dcounts, trg, ovf_vec = out
                with TraceAnnotation("tg.pull", site="fused"):
                    pulled = jax.device_get((n_counts, n_dcounts, trg,
                                             ovf_vec))
                ops.HOST_SYNC_STATS.fused_pulls += 1
            cnts, dcnts, trg, ovf = pulled
            if not ovf.any():
                budget.ok()
                stores = dict(zip(preds, n_stores))
                counts = {p: int(c) for p, c in zip(preds, cnts)}
                st.triggers += int(trg)
                new = {}
                for p, d, c in zip(derived, n_deltas, dcnts):
                    st.derived += int(c)
                    if int(c):
                        new[p] = (d, int(c))
                return new
            ops.HOST_SYNC_STATS.fused_retries += 1
            # a rule active at several delta positions repeats its join
            # labels; dedupe so a shared capacity doubles once per retry
            budget.overflow(dict.fromkeys(
                l for f, l in zip(ovf, ovf_labels) if f))
            for p in preds:
                stores[p] = ops.fit_rows(stores[p], caps.store[p])

    def drive():
        nonlocal deltas, progressed
        if resume is not None:
            st.extra["resumed"] = True
            for p, rows in resume.items():
                caps.seed_delta(p, len(rows))
                deltas[p] = (ops.fit_rows(rows, caps.delta_cap(p)),
                             len(rows))
        elif initial_deltas is None:
            # round 1: extensional rules over B
            ext_active = tuple((plan, None) for plan in ext_plans)
            if ext_active:
                deltas = run_round(ext_active, (), is_ext=True)
            st.rounds = 1
            progressed = True
            ck.boundary(st, state_fn, caps=caps)
        else:
            st.extra["delta"] = True
            for p, rel in initial_deltas.items():
                if rel.count:
                    caps.seed_delta(p, rel.count)
                    deltas[p] = (rel.data, rel.count)

        # fixpoint rounds
        while deltas and st.rounds < max_rounds:
            live = tuple(sorted(deltas))
            tail = _linear_tail(loop_plans, live)
            if tail is not None:
                s_preds, active = tail
                o_preds = tuple(p for p in preds if p not in s_preds)
                w = {p: None for p in s_preds}  # sorted tails (data, count)
                while True:
                    with TraceAnnotation("tg.fixpoint", round=st.rounds):
                        sig = _fix_signature(s_preds, o_preds, caps,
                                             active, use_prefilter, pallas,
                                             max_rounds, donate)
                        fn, ovf_labels = _cached_program(
                            sig, lambda: _build_fixpoint(
                                s_preds, o_preds, caps, active,
                                use_prefilter, pallas, max_rounds, donate))
                        out = fn(
                            tuple(stores[p] for p in s_preds),
                            tuple(jnp.array(ops.fit_rows(w[p][0],
                                                         caps.tail_cap(p)))
                                  if w[p] else
                                  jnp.full((caps.tail_cap(p),
                                            kb.arities[p]),
                                           kb.rels[p].pad, kb.rels[p].dtype)
                                  for p in s_preds),
                            tuple(jnp.int32(w[p][1] if w[p] else 0)
                                  for p in s_preds),
                            tuple(jnp.array(ops.fit_rows(deltas[p][0],
                                                         caps.delta_cap(p)))
                                  if p in deltas else
                                  jnp.full((caps.delta_cap(p),
                                            kb.arities[p]),
                                           kb.rels[p].pad, kb.rels[p].dtype)
                                  for p in s_preds),
                            tuple(jnp.int32(deltas[p][1] if p in deltas
                                            else 0)
                                  for p in s_preds),
                            tuple(stores[p] for p in o_preds),
                            jnp.int32(st.rounds))
                        w_datas, w_counts, d_datas, d_counts, rounds, trg, \
                            drv, ovf_vec = out
                        with TraceAnnotation("tg.pull", site="fused"):
                            pulled = jax.device_get((w_counts, d_counts,
                                                     rounds, trg, drv,
                                                     ovf_vec))
                        ops.HOST_SYNC_STATS.fused_pulls += 1
                    wcnts, dcnts, rounds, trg, drv, ovf = pulled
                    prev_rounds = st.rounds
                    st.rounds = int(rounds)
                    st.triggers += int(trg)
                    st.derived += int(drv)
                    deltas = {p: (d, int(c)) for p, d, c in
                              zip(s_preds, d_datas, dcnts) if int(c)}
                    # fold tails into the stores (exits are rare: done, a
                    # full tail, or a capacity retry)
                    ar = kb.arities
                    with TraceAnnotation("tg.fold"):
                        for p, d, c in zip(s_preds, w_datas, wcnts):
                            w[p] = None
                            if int(c):
                                merged = ops.merge_union(
                                    Relation(stores[p], counts[p],
                                             lex_order(ar[p])),
                                    Relation(d, int(c), lex_order(ar[p])))
                                counts[p] = merged.count
                                caps.store[p] = max(caps.store[p],
                                                    merged.capacity)
                                stores[p] = ops.fit_rows(merged.data,
                                                         caps.store[p])
                    if st.rounds > prev_rounds:
                        budget.ok()     # the loop advanced: real progress
                        progressed = True
                    ck.boundary(st, state_fn, caps=caps)
                    if not ovf.any():
                        deltas = {}
                        break
                    to_double = []
                    for flag, label in zip(ovf, ovf_labels):
                        if not flag:
                            continue
                        if label[0] == "tail" and \
                                int(wcnts[s_preds.index(label[1])]) != 0:
                            # tail-full exit: the fold above made room;
                            # double only when even an empty tail cannot
                            # hold one round's fresh rows
                            continue
                        to_double.append(label)
                    if to_double:
                        ops.HOST_SYNC_STATS.fused_retries += 1
                        budget.overflow(dict.fromkeys(to_double))
                break
            active = tuple((plans[id(r)], j)
                           for r in loop_rules
                           for j, a in enumerate(r.body)
                           if a.pred in deltas)
            if not active:
                break
            deltas = run_round(active, live)
            st.rounds += 1
            progressed = True
            ck.boundary(st, state_fn, caps=caps)

    try:
        drive()
    except CapacityError as e:
        if not spill:
            raise
        if not progressed:
            return None     # cold-start overflow: plain fragment fallback
        # graceful degradation: write the last-good state back and run the
        # remaining rounds on the two-phase executor, whose buffers grow
        # incrementally instead of by whole-plan doubling
        from repro.engine.materialize import _fixpoint_rounds
        for p in preds:
            kb.rels[p] = Relation(stores[p], counts[p],
                                  lex_order(kb.rels[p].arity))
        seed = {}
        for p, (d, c) in deltas.items():
            rows = np.asarray(d)[:int(c)]
            seed[p] = Relation.from_numpy(
                rows[np.lexsort(rows.T[::-1])],
                sorted_by=lex_order(kb.arities[p]))
        st.extra["spilled"] = str(e)
        _fixpoint_rounds(kb, st, seed, mode, max_rounds, ck=ck)
        return st

    for p in preds:
        kb.rels[p] = Relation(stores[p], counts[p],
                              lex_order(kb.rels[p].arity))
    caps.memoize()
    ck.final(st, state_fn, caps=caps)
    return st


# ---------------------------------------------------------------------------
# program lowering for the roofline analysis (no execution)
# ---------------------------------------------------------------------------
def lower_fused_programs(kb, mode: str = "tg"):
    """Lower (without running) the fused executor's programs for ``kb`` at
    the capacity planner's current shapes: ``{name: (hlo_text,
    cost_analysis)}`` for the steady-state round program and — when the
    program has a linear tail — the while_loop fixpoint program.

    This is what ``analysis.roofline`` feeds to the trip-count-aware HLO
    walk to publish bytes/flops-per-fact for the actual executable the
    benchmarks time.  Call it AFTER a real materialization so the capacity
    memo holds converged buckets (the planner then reproduces the shapes
    the timed run compiled at).  Returns None outside the fused fragment."""
    import numpy as np

    program = kb.program
    plans = {}
    for rule in program.rules:
        plan = compile_rule_plan(rule, kb.dict)
        if plan is None:
            return None
        plans[id(rule)] = plan
    preds = tuple(sorted(kb.rels))
    use_prefilter = mode == "tg"
    pallas = ops.use_pallas()
    fp = program_fingerprint((plans[id(r)].key for r in program.rules),
                             sum(kb.rels[p].count for p in preds))
    caps = _Caps(fp, {p: (kb.rels[p].data, kb.rels[p].count) for p in preds})
    loop_plans = [plans[id(r)] for r in program.rules]
    derived = {pl.head_pred for pl in loop_plans}
    active = tuple((plans[id(r)], j) for r in program.rules
                   for j, a in enumerate(r.body) if a.pred in derived)
    if not active:
        return {}

    def rel_aval(cap, p):
        return jax.ShapeDtypeStruct((cap, kb.arities[p]), kb.rels[p].dtype)

    i32 = jax.ShapeDtypeStruct((), np.int32)

    def lowered_pair(fn, *avals):
        compiled = fn.lower(*avals).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        return compiled.as_text(), dict(cost or {})

    out = {}
    delta_in = tuple(sorted({plan.body_preds[jd] for plan, jd in active}))
    fn, _, _ = _build_round(preds, caps, active, delta_in, use_prefilter,
                            pallas)
    out["round"] = lowered_pair(
        fn,
        tuple(rel_aval(caps.store[p], p) for p in preds),
        tuple(i32 for _ in preds),
        tuple(rel_aval(caps.delta_cap(p), p) for p in delta_in))
    # the fixpoint's steady-state live set is usually smaller than the
    # early-round one (aux predicates quiesce): fall back to singleton live
    # sets so the lowered fixpoint matches the phase the driver actually
    # spends its time in
    tail = _linear_tail(loop_plans, delta_in)
    if tail is None:
        for p in sorted(derived):
            tail = _linear_tail(loop_plans, (p,))
            if tail is not None:
                break
    if tail is not None:
        s_preds, t_active = tail
        o_preds = tuple(p for p in preds if p not in s_preds)
        ffn, _ = _build_fixpoint(s_preds, o_preds, caps, t_active,
                                 use_prefilter, pallas, 10_000, False)
        out["fixpoint"] = lowered_pair(
            ffn,
            tuple(rel_aval(caps.store[p], p) for p in s_preds),
            tuple(rel_aval(caps.tail_cap(p), p) for p in s_preds),
            tuple(i32 for _ in s_preds),
            tuple(rel_aval(caps.delta_cap(p), p) for p in s_preds),
            tuple(i32 for _ in s_preds),
            tuple(rel_aval(caps.store[p], p) for p in o_preds),
            i32)
    return out
