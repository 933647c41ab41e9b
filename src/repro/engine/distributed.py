"""Distributed materialization: a ``shard_map`` executor over the shared
rule-plan IR (beyond-paper: the paper lists distributed KBs as future work).

This is the third physical executor over ``repro.engine.plan``'s
:class:`RulePlan` IR — the same plans the fused single-device executor
compiles, run over hash-partitioned shards.  It handles *arbitrary* Datalog
programs in the plannable fragment (no existentials, connected bodies), not
just the hand-written transitive closure the first version shipped with.

Data model (:class:`ShardedKB` state, kept as device arrays between
rounds): every predicate's store is partitioned across the mesh ``axis`` by
the full-tuple hash — the canonical home of a fact is the shard its hash
picks, which makes dedup and the antijoin against the store purely local —
and each shard keeps its rows lexsorted (the same ``Relation.sorted_by``
store invariant as the single-device engine, so the shared ops cores skip
their sort passes on store inputs).

Each semi-naive / TG round compiles to ONE ``shard_map`` program (cached by
its static signature) that:

  1. walks every active ``(rule plan, delta position)`` with the shared
     chain walker ``_exec_rule_traced``, passing a ``route`` hook that
     re-partitions rows by join key before each join side (and by projected
     head-tuple hash before the Def. 23 antijoin pre-restriction) via the
     fixed-capacity bucket ``_exchange`` (``all_to_all``),
  2. re-partitions each predicate's derivations by full-tuple hash so
     duplicates land on one shard, then runs the shared ``_absorb_traced``
     (lexsort + dedup + antijoin vs the local store shard + incremental
     sorted merge) locally,
  3. reduces convergence scalars with ``psum``: per-pred fresh-fact totals,
     the trigger total, and the overflow vector.

The host pulls exactly one scalar bundle per round attempt
(``HOST_SYNC_STATS.dist_pulls``) regardless of the shard count — and, once
the remaining program is *linear* (``plan._linear_tail``), the driver stops
stepping rounds from the host at all: the whole fixpoint phase compiles to
ONE ``lax.while_loop``-under-``shard_map`` program
(:func:`_build_dist_fixpoint`) whose convergence check is an on-device
``psum`` folded into the loop carry.  The host then pulls once per
*phase exit* (``HOST_SYNC_STATS.dist_fixpoint_pulls``) — fixpoint reached,
a tail buffer filled (fold, double, resume), or a capacity overflow — instead of
once per round, which is what makes ``dist_pulls`` O(phases) rather than
O(rounds).  Inside the loop, communication overlaps compute: the delta
exchange feeding iteration k+1 is issued at the end of iteration k
(software-pipelined through the carry, dependency-free of the tail merges,
so XLA can run the ``all_to_all`` concurrently with the merge arithmetic),
loop-invariant store-side exchanges are hoisted out of the loop entirely,
and the Def. 23 pre-restriction routing rides the same overlapped window
when it sits on the delta atom.  ``REPRO_DIST_FIXPOINT=0`` forces the
host-stepped per-round path for A/B comparison.

Overflow follows the planner contract from ``repro.engine.plan``: every
planned capacity (store / delta / tail / join / exchange bucket, all per
shard) carries an in-program flag; when any fires the round's (or loop
iteration's) outputs are rolled back to the last good state, the host
doubles exactly the overflowed buckets, recompiles, and retries — a
host-stepped round retry counts in ``HOST_SYNC_STATS.dist_retries``, while
fixpoint-phase capacity retries and tail folds surface as extra
``dist_fixpoint_pulls``, so the two causes stay distinguishable.

Pallas routing is pinned off here: the kernels are not shard_map-
transformable in interpret mode.

Tracing: the programs are jitted as ``tg_dist_round`` and
``tg_dist_fixpoint``; ``_exchange`` runs under the ``tg.exchange`` scope and
``_merge_runs`` under ``tg.merge``; the host steps are the fused executor's
spans (``tg.round``, ``tg.fixpoint``, ``tg.fold``) with ``tg.pull`` at site
``dist``.

Entry points: ``materialize(kb, mode="tg", backend="dist")`` (or
``REPRO_DIST=1``) routes through :func:`materialize_distributed`, falling
back to the fused / two-phase executors for programs outside the fragment;
``run_distributed_tc`` is the back-compat TC wrapper; ``lower_distributed_tc``
lowers one TC round on a target mesh for the multi-pod dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.engine import ops, recovery
from repro.engine.plan import (_absorb_traced, _cached_program, _Caps,
                               _exec_rule_traced, _linear_tail,
                               _select_state, CapacityError,
                               compile_rule_plan, program_fingerprint,
                               RetryBudget)
from repro.engine.relation import Relation, lex_order, pad_of, pad_value
from repro.launch.mesh import axis_size


# ---------------------------------------------------------------------------
# hashing (device + host mirrors must agree: initial placement partitions on
# the host with the same function the exchanges use on device)
# ---------------------------------------------------------------------------
def _hash32(x):
    """Cheap int32 mix (Wang hash variant, stays in int32)."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return (x ^ (x >> 16)).astype(jnp.uint32)


def _cols_hash(rows, cols):
    """Combined hash of the given columns of each row (uint32)."""
    h = jnp.uint32(0x9E3779B9)
    for c in cols:
        h = _hash32(rows[:, c].astype(jnp.uint32) + h)
    return h


def _tuple_hash(rows):
    return _cols_hash(rows, range(rows.shape[1]))


def _np_hash32(x):
    x = x.astype(np.uint32)
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def np_tuple_hash(rows: np.ndarray) -> np.ndarray:
    """Host mirror of ``_tuple_hash`` for the initial placement."""
    h = np.uint32(0x9E3779B9)
    out = np.full(rows.shape[0], h, np.uint32)
    for c in range(rows.shape[1]):
        out = _np_hash32(rows[:, c].astype(np.uint32) + out)
    return out


# ---------------------------------------------------------------------------
# fixed-capacity bucket exchange
# ---------------------------------------------------------------------------
def _route_to_buckets(rows, target, ndev, bucket_cap, sort_cols=None):
    """Pure bucketization half of ``_exchange`` (property-tested on its
    own): scatter rows into per-destination buckets of ``bucket_cap`` rows,
    preserving input order within each bucket (``argsort`` is stable).
    Invalid (PAD) rows are discarded; valid rows beyond a destination's
    capacity are counted.  With ``sort_cols`` (a column sequence) the
    within-bucket order becomes lexicographic by those columns instead of
    input order — one composite (destination, cols...) lexsort, no costlier
    than the plain destination argsort, which hands every receiver
    pre-sorted runs (see ``_merge_runs``).  Returns ((ndev, bucket_cap, ar)
    buckets, overflow_count)."""
    cap, ar = rows.shape
    valid = rows[:, 0] != pad_of(rows)
    target = jnp.where(valid, target, ndev)          # invalid -> trash bucket
    if sort_cols is None:
        order = jnp.argsort(target)
    else:                 # lexsort: LAST key is primary -> target, then cols
        order = jnp.lexsort(tuple(rows[:, c] for c in reversed(sort_cols))
                            + (target,))
    t_sorted = target[order]
    rows_sorted = rows[order]
    pos = jnp.arange(cap) - jnp.searchsorted(t_sorted, t_sorted, side="left")
    slot = jnp.where(t_sorted < ndev, t_sorted * bucket_cap + pos,
                     ndev * bucket_cap)
    overflow = jnp.logical_and(t_sorted < ndev, pos >= bucket_cap)
    slot = jnp.where(overflow, ndev * bucket_cap, slot)
    buckets = jnp.full((ndev * bucket_cap + 1, ar), pad_of(rows), rows.dtype)
    buckets = buckets.at[slot].set(jnp.where((t_sorted < ndev)[:, None],
                                             rows_sorted, pad_of(rows)),
                                   mode="drop")
    return (buckets[:ndev * bucket_cap].reshape(ndev, bucket_cap, ar),
            jnp.sum(overflow))


@jax.named_scope("tg.exchange")
def _exchange(rows, target, ndev, axis, bucket_cap, sort_cols=None):
    """Fixed-capacity bucket exchange: rows (cap, ar) with target shard ids;
    rows routed via all_to_all; returns ((ndev*bucket_cap, ar) local rows,
    dropped_count) — overflowed rows are counted, so the driver can retry
    with bigger buckets.  ``sort_cols`` orders each bucket by those columns
    before sending (``_route_to_buckets``), so the received block is
    ``ndev`` front-packed sorted runs."""
    buckets, overflow = _route_to_buckets(rows, target, ndev, bucket_cap,
                                          sort_cols=sort_cols)
    recv = jax.lax.all_to_all(buckets, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    return recv.reshape(ndev * bucket_cap, rows.shape[1]), overflow


_MERGE_MAX_WAYS = 4      # ndev**2 pairwise rank probes beat a sort up to here


@jax.named_scope("tg.merge")
def _merge_runs(blk, ndev, perm):
    """Merge the ``ndev`` per-source sorted runs of an exchanged block into
    one front-packed block lexsorted in ``perm`` column order (``perm`` is
    the full column permutation the sender sorted by, key columns first).

    The sender's composite bucketize sort already ordered every bucket, so
    the receiver only has to merge: a rank-based k-way merge — each row's
    output slot is its index within its run plus one ``searchsorted`` count
    against every other run (ties broken by source-run index, so slots are
    unique), landed with a single scatter.  That is ndev*(ndev-1) binary
    searches over scalar keys (``ops.scalar_key``) instead of an O(n log n)
    re-sort of the whole block; at ndev=1 the block is already fully sorted
    and nothing runs at all.  Past ``_MERGE_MAX_WAYS`` runs (or rows with
    no scalar key) the
    quadratic probe count loses to XLA's sort, so it falls back to one full
    lexsort — same contract, no pre-sorted-run benefit."""
    n, ar = blk.shape
    identity = tuple(perm) == tuple(range(ar))
    if ndev == 1:
        return blk
    cap = n // ndev
    rot = blk if identity else blk[:, list(perm)]
    runs = [rot[i * cap:(i + 1) * cap] for i in range(ndev)]
    keys = [ops.scalar_key(r) for r in runs]
    if ndev > _MERGE_MAX_WAYS or keys[0] is None:
        out = ops.lexsort_core(rot, pallas=False)
    else:
        valids = [blk[i * cap:(i + 1) * cap, 0] != pad_of(blk)
                  for i in range(ndev)]
        iota = jnp.arange(cap, dtype=jnp.int32)
        ranks = []
        for i in range(ndev):
            rank = iota
            for j in range(ndev):
                if j == i:
                    continue
                # right for earlier runs / left for later ones: equal
                # rows order by source run, making every slot unique
                rank = rank + jnp.searchsorted(
                    keys[j], keys[i],
                    side="right" if j < i else "left").astype(jnp.int32)
            ranks.append(rank)
        out = jnp.full((n + 1, ar), pad_of(blk), blk.dtype)
        for i, r in enumerate(runs):
            pos = jnp.where(valids[i], ranks[i], n)    # PAD rows -> trash
            out = out.at[pos].set(jnp.where(valids[i][:, None], r,
                                            pad_of(blk)),
                                  mode="drop")
        out = out[:n]
    if identity:
        return out
    inv = [0] * ar
    for i, c in enumerate(perm):
        inv[c] = i
    return out[:, inv]


@dataclass(frozen=True)
class DistConfig:
    """Fixed capacities for the dry-run / back-compat entries (the general
    executor plans its own per-shard capacities via ``plan._Caps``)."""
    shard_cap: int = 1 << 14         # per-shard store capacity
    delta_cap: int = 1 << 12         # per-shard delta capacity
    bucket_cap: int = 1 << 9         # per-destination exchange bucket
    max_rounds: int = 64
    axis: tuple = ("data",)          # mesh axes facts are partitioned over


# ---------------------------------------------------------------------------
# overflow-label enumeration (must mirror the flag order the traced round
# emits: _exec_rule_traced appends pre / left / right exchange flags then
# the join-capacity flag, per join step)
# ---------------------------------------------------------------------------
def _rule_ovf_labels(plan, use_pre):
    labels = []
    for j in range(len(plan.atoms)):
        if use_pre and plan.pre is not None and plan.pre[0] == j:
            labels.append(("bucket", (plan.key, "pre", j)))
        if j >= 1:
            labels.append(("bucket", (plan.key, "jl", j)))
            labels.append(("bucket", (plan.key, "jr", j)))
            labels.append(("join", (plan.key, j - 1)))
    return labels


def _round_ovf_labels(active, use_prefilter, derived):
    labels = []
    for plan, _ in active:
        labels += _rule_ovf_labels(plan, use_prefilter)
    for pred in derived:
        labels += [("bucket", ("absorb", pred)),
                   ("delta", pred), ("store", pred)]
    return labels


def _bucket_keys(labels):
    return tuple(name for kind, name in labels if kind == "bucket")


# ---------------------------------------------------------------------------
# compiled sharded round program
# ---------------------------------------------------------------------------
def _dist_signature(mesh, axis, ndev, preds, caps, active, delta_in,
                    use_prefilter):
    derived = tuple(sorted({plan.head_pred for plan, _ in active}))
    labels = _round_ovf_labels(active, use_prefilter, derived)
    return ("dist_round", mesh, axis, ndev, preds,
            tuple(caps.store[p] for p in preds),
            tuple((plan.key, jd, tuple(caps.join_cap(plan, i)
                                       for i in range(len(plan.joins))))
                  for plan, jd in active),
            tuple((p, caps.delta_cap(p)) for p in delta_in),
            tuple((p, caps.delta_cap(p)) for p in derived),
            tuple((k, caps.bucket_cap(k)) for k in _bucket_keys(labels)),
            use_prefilter)


def _build_dist_round(mesh, axis, ndev, preds, caps, active, delta_in,
                      use_prefilter):
    """One sharded materialization round as a single jitted shard_map
    program.

    Per-shard inputs: store blocks (tuple-hash partitioned, lexsorted, at
    planner capacities) + per-shard counts, plus the live delta blocks.
    Outputs: new stores / counts / deltas (per shard), the psum'd per-pred
    fresh totals, the round's global trigger total, and the psum'd overflow
    vector.  ``ovf_labels`` names each overflow slot so the driver can
    double exactly the right capacity."""
    derived = tuple(sorted({plan.head_pred for plan, _ in active}))
    ovf_labels = _round_ovf_labels(active, use_prefilter, derived)
    join_caps = {id(plan): tuple(caps.join_cap(plan, i)
                                 for i in range(len(plan.joins)))
                 for plan, _ in active}
    delta_caps = {p: caps.delta_cap(p) for p in derived}
    bucket_caps = {k: caps.bucket_cap(k) for k in _bucket_keys(ovf_labels)}

    def tg_dist_round(store_datas, store_counts, delta_datas):
        stores = dict(zip(preds, store_datas))
        counts = {p: c[0] for p, c in zip(preds, store_counts)}
        deltas = dict(zip(delta_in, delta_datas))
        triggers = jnp.zeros((), jnp.int32)
        ovfs = []
        heads = {}
        for plan, jd in active:
            def route(rows, cols, tag, _pk=plan.key):
                cap = bucket_caps[(_pk, *tag)]
                tgt = (_cols_hash(rows, cols)
                       % jnp.uint32(ndev)).astype(jnp.int32)
                out, dropped = _exchange(rows, tgt, ndev, axis, cap)
                return out, [dropped > 0], None
            inputs = [deltas[bp] if j == jd else stores[bp]
                      for j, bp in enumerate(plan.body_preds)]
            pre_data = stores[plan.head_pred] if use_prefilter else None
            head, trg, flags = _exec_rule_traced(
                plan, inputs, pre_data, join_caps[id(plan)], False,
                route=route)
            triggers += trg
            ovfs += flags
            heads.setdefault(plan.head_pred, []).append(head)
        out_deltas, out_dcounts, fresh_tot = [], [], []
        for pred in derived:
            hs = heads[pred]
            cat = hs[0] if len(hs) == 1 else jnp.concatenate(hs, axis=0)
            # canonical-home repartition: duplicates of a tuple (across
            # rules AND shards) all land on the shard its hash picks, so
            # dedup + the antijoin against the store are local
            tgt = (_tuple_hash(cat) % jnp.uint32(ndev)).astype(jnp.int32)
            routed, dropped = _exchange(cat, tgt, ndev, axis,
                                        bucket_caps[("absorb", pred)])
            ovfs.append(dropped > 0)
            ns, nc, delta, nf, (od, os_) = _absorb_traced(
                [routed],
                lambda rows, p=pred: jnp.logical_not(
                    ops.member_mask_core(rows, stores[p])),
                stores[pred], counts[pred], delta_caps[pred], False)
            stores[pred] = ns
            counts[pred] = nc
            out_deltas.append(delta)
            out_dcounts.append(nf)
            fresh_tot.append(jax.lax.psum(nf, axis))
            ovfs += [od, os_]
        ovf_vec = (jnp.stack(ovfs).astype(jnp.int32) if ovfs
                   else jnp.zeros((0,), jnp.int32))
        return (tuple(stores[p] for p in preds),
                tuple(counts[p].reshape(1) for p in preds),
                tuple(out_deltas),
                tuple(nf.reshape(1) for nf in out_dcounts),
                tuple(fresh_tot),
                jax.lax.psum(triggers, axis),
                jax.lax.psum(ovf_vec, axis))

    in_specs = (tuple(P(axis, None) for _ in preds),
                tuple(P(axis) for _ in preds),
                tuple(P(axis, None) for _ in delta_in))
    out_specs = (tuple(P(axis, None) for _ in preds),
                 tuple(P(axis) for _ in preds),
                 tuple(P(axis, None) for _ in derived),
                 tuple(P(axis) for _ in derived),
                 tuple(P() for _ in derived),
                 P(), P())
    fn = jax.jit(jax.shard_map(tg_dist_round, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs))
    return fn, ovf_labels, derived


# ---------------------------------------------------------------------------
# compiled linear-tail fixpoint program (lax.while_loop under shard_map)
# ---------------------------------------------------------------------------
def _site_route_tag(plan, jd, use_pre):
    """The exchange tag through which one linear-fixpoint site's DELTA
    first flows — the exchange that gets software-pipelined through the
    loop carry — or None when the site routes nothing delta-side
    (single-atom rule without a usable pre-restriction: its heads only
    move in the absorb exchange)."""
    if use_pre and plan.pre is not None and plan.pre[0] == jd:
        return ("pre", jd)
    if len(plan.atoms) == 1:
        return None
    return ("jl", 1) if jd == 0 else ("jr", jd)


def _site_tags(plan, jd, use_pre):
    """Exchange tags of one linear-fixpoint site (plan with the delta at
    body position ``jd``), in the exact order ``_exec_rule_traced``
    reaches them.  Returns ``(carried_tag, [(tag, kind, key_cols)])``
    where kind is:

    * ``'carried'`` — the first delta-side exchange: its routed block is
      produced at the END of the previous loop iteration (right after the
      fresh delta materializes, with no dependency on the tail merges, so
      the ``all_to_all`` overlaps them) and rides the loop carry,
    * ``'static'`` — routes a loop-invariant store input: hoisted out of
      the loop and exchanged once per fixpoint attempt,
    * ``'live'`` — routes delta-derived rows mid-chain: stays in-loop.
    """
    pre_j = plan.pre[0] if (use_pre and plan.pre is not None) else None
    carried = _site_route_tag(plan, jd, use_pre)
    tags = []
    for j in range(len(plan.atoms)):
        if pre_j == j:
            kind = "carried" if ("pre", j) == carried else "static"
            tags.append((("pre", j), kind, plan.pre[1]))
        if j >= 1:
            lk, rk, _ = plan.joins[j - 1]
            if ("jl", j) == carried:
                kind = "carried"
            elif j == 1 and jd >= 1 and pre_j != 0:
                kind = "static"        # left side of join 1 is a store
            else:
                kind = "live"
            tags.append((("jl", j), kind, (lk,)))
            if ("jr", j) == carried:
                kind = "carried"
            elif j != jd and pre_j != j:
                kind = "static"        # right side is an unfiltered store
            else:
                kind = "live"
            tags.append((("jr", j), kind, (rk,)))
    return carried, tags


def _fix_ovf_labels(active, use_pre, derived):
    """Overflow labels of the fixpoint program, partitioned into its three
    emission groups: *body* (in-loop flags, in traced emission order: live
    exchanges + join caps per site, then absorb-bucket / delta / tail per
    derived pred), *production* (the carried delta-side exchanges, one per
    site that has one, in site order — emitted in-loop after the absorbs),
    and *static* (the hoisted store-side exchanges, emitted once before
    the loop).  The program's overflow vector is body ++ production ++
    static."""
    body, production, static = [], [], []
    for plan, jd in active:
        _, tags = _site_tags(plan, jd, use_pre)
        for tag, kind, _cols in tags:
            label = ("bucket", (plan.key, *tag))
            {"live": body, "carried": production,
             "static": static}[kind].append(label)
            if tag[0] == "jr":
                body.append(("join", (plan.key, tag[1] - 1)))
    for pred in derived:
        body += [("bucket", ("absorb", pred)), ("delta", pred),
                 ("tail", pred)]
    return body, production, static


def _dist_fix_signature(mesh, axis, ndev, s_preds, o_preds, caps, active,
                        use_prefilter, max_rounds):
    derived = tuple(sorted({plan.head_pred for plan, _ in active}))
    body, prod, static = _fix_ovf_labels(active, use_prefilter, derived)
    bkeys = tuple(name for kind, name in body + prod + static
                  if kind == "bucket")
    return ("dist_fix", mesh, axis, ndev, s_preds, o_preds,
            tuple(caps.store[p] for p in s_preds + o_preds),
            tuple(caps.delta_cap(p) for p in s_preds),
            tuple(caps.tail_cap(p) for p in s_preds),
            tuple((plan.key, jd, tuple(caps.join_cap(plan, i)
                                       for i in range(len(plan.joins))))
                  for plan, jd in active),
            tuple((k, caps.bucket_cap(k)) for k in bkeys),
            use_prefilter, max_rounds)


def _build_dist_fixpoint(mesh, axis, ndev, s_preds, o_preds, caps, active,
                         use_prefilter, max_rounds):
    """The remaining (linear) fixpoint as ONE sharded program: a
    ``lax.while_loop`` whose body is a whole distributed round, with the
    convergence check folded into the carry as on-device ``psum``s — zero
    host pulls until fixpoint, overflow, or ``max_rounds``.

    Cross-shard termination uniformity: everything the loop condition
    reads (live count, round counter, overflow vector) is psum'd in the
    body, so every shard takes the same branch each iteration (a
    collective in the condition itself would be illegal).

    The round body mirrors the fused fixpoint (phase-entry stores as loop
    constants, per-pred sorted tail buffers, probe store | tail, last-good
    rollback on overflow via ``_select_state``) with the distributed
    exchanges layered on per ``_site_tags``: static store-side routes are
    hoisted above the loop, the delta-side route is software-pipelined —
    iteration k closes by sort-bucketizing + exchanging + run-merging the
    delta it just produced, a computation independent of its tail merges, and
    the routed block enters iteration k+1 through the carry (the
    compute/comm-overlap window; the Def. 23 pre-restriction's
    projected-head-hash routing rides it whenever the pre-restriction
    sits on the delta atom).

    Exits return per-shard tails + counts, per-shard deltas + counts, and
    the psum'd rounds / triggers / derived / overflow scalars; the host
    folds tails into the store shards, doubles exactly the overflowed
    capacities, and resumes mid-fixpoint."""
    derived = tuple(sorted({plan.head_pred for plan, _ in active}))
    body_labels, prod_labels, static_labels = _fix_ovf_labels(
        active, use_prefilter, derived)
    ovf_labels = body_labels + prod_labels + static_labels
    n_body, n_static = len(body_labels), len(static_labels)
    sites = []
    carried_slot = {}                  # site index -> carry tuple slot
    site_cols = {}                     # site index -> carried key cols
    site_skey = {}                     # site index -> static sort key
    for plan, jd in active:
        carried, tags = _site_tags(plan, jd, use_prefilter)
        si = len(sites)
        if carried is not None:
            carried_slot[si] = len(carried_slot)
            cols = next(c for t, _k, c in tags if t == carried)
            site_cols[si] = cols
            # join-side blocks are pre-sorted by the join key at
            # production time (inside the overlap window), so the in-loop
            # chain skips its keysort; pre-restriction blocks are probed,
            # not joined, and need no order
            site_skey[si] = cols[0] if carried[0] != "pre" else None
        sites.append((plan, jd, carried, tags))
    join_caps = {id(plan): tuple(caps.join_cap(plan, i)
                                 for i in range(len(plan.joins)))
                 for plan, _ in active}
    delta_caps = {p: caps.delta_cap(p) for p in s_preds}
    tail_caps = {p: caps.tail_cap(p) for p in s_preds}
    bucket_caps = {name: caps.bucket_cap(name)
                   for kind, name in ovf_labels if kind == "bucket"}

    def exch(rows, cols, key, sort=False):
        tgt = (_cols_hash(rows, cols) % jnp.uint32(ndev)).astype(jnp.int32)
        if not sort:
            out, dropped = _exchange(rows, tgt, ndev, axis, bucket_caps[key])
            return out, dropped > 0
        # sorted exchange: the sender lexsorts each bucket by (cols, rest)
        # inside the composite bucketize sort, the receiver tree-merges the
        # ndev runs — log2(ndev) linear passes replace the post-exchange
        # O(n log n) keysort, and the merged block satisfies the join's
        # skey contract (sorted by cols[0])
        perm = tuple(cols) + tuple(c for c in range(rows.shape[1])
                                   if c not in cols)
        out, dropped = _exchange(rows, tgt, ndev, axis, bucket_caps[key],
                                 sort_cols=perm)
        return _merge_runs(out, ndev, perm), dropped > 0

    def filt(plan, j, data):
        """Atom-j filters on a raw block (production-side routing must see
        the same rows the in-loop chain would route)."""
        eq, consts = plan.atoms[j]
        if eq or consts:
            mask = ops.filter_mask_core(data, eq, consts)
            data = ops.compact_core(data, mask, data.shape[0])
        return data

    def tg_dist_fixpoint(s_datas, d_datas, o_datas, rounds0):
        base = dict(zip(s_preds, s_datas))
        others = dict(zip(o_preds, o_datas))
        deltas0 = dict(zip(s_preds, d_datas))

        def not_seen(rows, pred, tails, cols=None):
            """keep-mask: rows whose (projected) tuple is in neither the
            phase-entry store shard nor the tail shard of ``pred`` —
            callers route rows by the projected tuple's hash first, so
            the canonical-home shard answers membership locally."""
            sel = rows if cols is None else ops.project_core(rows, cols)
            seen = jnp.logical_or(
                ops.member_mask_core(sel, base[pred]),
                ops.member_mask_core(sel, tails[pred]))
            valid = rows[:, 0] != pad_of(rows)
            return jnp.logical_and(valid, jnp.logical_not(seen))

        # hoisted loop-invariant store-side exchanges: routed (and
        # key-sorted) once per fixpoint attempt, loop constants thereafter
        static_routed = {}
        static_flags = []
        for plan, jd, carried, tags in sites:
            for tag, kind, cols in tags:
                if kind != "static":
                    continue
                src_j = 0 if tag[0] == "jl" else tag[1]
                blk, flag = exch(filt(plan, src_j,
                                      others[plan.body_preds[src_j]]),
                                 cols, (plan.key, *tag),
                                 sort=tag[0] != "pre")
                skey = cols[0] if tag[0] != "pre" else None
                static_routed[(id(plan), tag)] = (blk, skey)
                static_flags.append(flag)

        def produce_carried(si, plan, jd, carried, fresh_delta):
            """The overlapped production of one site's next-iteration
            input: filter + sorted-exchange the fresh delta (pre-restriction
            blocks are probed, not joined, so they skip the sort)."""
            return exch(filt(plan, jd, fresh_delta), site_cols[si],
                        (plan.key, *carried), sort=site_skey[si] is not None)

        carried0, prod_flags = [], []
        for si, (plan, jd, carried, tags) in enumerate(sites):
            if carried is None:
                continue
            blk, flag = produce_carried(si, plan, jd, carried,
                                        deltas0[plan.body_preds[jd]])
            carried0.append(blk)
            prod_flags.append(flag)

        init_flags = prod_flags + static_flags
        ovf0 = jnp.concatenate([
            jnp.zeros((n_body,), jnp.int32),
            (jax.lax.psum(jnp.stack(init_flags).astype(jnp.int32), axis)
             if init_flags else jnp.zeros((0,), jnp.int32))])
        d_counts0 = tuple(jnp.sum(deltas0[p][:, 0] != pad_of(deltas0[p])
                                  ).astype(jnp.int32)
                          for p in s_preds)
        live0 = jax.lax.psum(sum(d_counts0), axis)

        def body(state):
            (w_datas, w_counts, d_datas, d_counts, carried_blks, rounds,
             trg, drv, live, _ovf) = state
            tails = dict(zip(s_preds, w_datas))
            wcnt = dict(zip(s_preds, w_counts))
            deltas = dict(zip(s_preds, d_datas))
            triggers = jnp.zeros((), jnp.int32)
            ovfs = []
            heads = {}
            for si, (plan, jd, carried, tags) in enumerate(sites):
                def route(rows, cols, tag, _plan=plan, _carried=carried,
                          _si=si):
                    if tag == _carried:
                        return (carried_blks[carried_slot[_si]], [],
                                site_skey[_si])
                    hit = static_routed.get((id(_plan), tag))
                    if hit is not None:
                        return hit[0], [], hit[1]
                    # live tags are always join sides (_site_tags never
                    # marks a pre tag live), so the sorted exchange lets
                    # the chain skip its keysort too
                    out, flag = exch(rows, cols, (_plan.key, *tag),
                                     sort=True)
                    return out, [flag], cols[0]

                inputs = [deltas[bp] if j == jd else others[bp]
                          for j, bp in enumerate(plan.body_preds)]
                pf = ((lambda rows, cols, p=plan.head_pred:
                       not_seen(rows, p, tails, cols))
                      if use_prefilter and plan.pre is not None else None)
                head, t, flags = _exec_rule_traced(
                    plan, inputs, None, join_caps[id(plan)], False,
                    prefilter=pf, route=route)
                triggers += t
                ovfs += flags
                heads.setdefault(plan.head_pred, []).append(head)
            new_w, new_wc, new_d, new_dc = {}, {}, {}, {}
            for pred in s_preds:
                if pred in heads:
                    hs = heads[pred]
                    cat = (hs[0] if len(hs) == 1
                           else jnp.concatenate(hs, axis=0))
                    tgt = (_tuple_hash(cat)
                           % jnp.uint32(ndev)).astype(jnp.int32)
                    # full-lex sorted exchange: the absorb's own lexsort
                    # collapses to the run merge (presorted=True below)
                    lex = tuple(range(cat.shape[1]))
                    routed, dropped = _exchange(
                        cat, tgt, ndev, axis,
                        bucket_caps[("absorb", pred)], sort_cols=lex)
                    routed = _merge_runs(routed, ndev, lex)
                    ovfs.append(dropped > 0)
                    nw, nc, delta, nf, (od, ow) = _absorb_traced(
                        [routed],
                        lambda rows, p=pred: not_seen(rows, p, tails),
                        tails[pred], wcnt[pred], delta_caps[pred], False,
                        presorted=True)
                    new_w[pred], new_wc[pred] = nw, nc
                    new_d[pred], new_dc[pred] = delta, nf
                    ovfs += [od, ow]
                else:           # in S but not derived by any site: drains
                    new_w[pred] = tails[pred]
                    new_wc[pred] = wcnt[pred]
                    new_d[pred] = jnp.full_like(deltas[pred],
                                                pad_of(deltas[pred]))
                    new_dc[pred] = jnp.zeros((), jnp.int32)
            # overlapped production for iteration k+1: depends only on the
            # fresh deltas, NOT on the tail merges above, so the exchange
            # runs concurrently with them and the routed block enters the
            # next iteration through the carry
            new_carried = []
            for si, (plan, jd, carried, tags) in enumerate(sites):
                if carried is None:
                    continue
                blk, flag = produce_carried(si, plan, jd, carried,
                                            new_d[plan.body_preds[jd]])
                new_carried.append(blk)
                ovfs.append(flag)
            ovf_vec = jnp.concatenate([
                (jax.lax.psum(jnp.stack(ovfs).astype(jnp.int32), axis)
                 if ovfs else jnp.zeros((0,), jnp.int32)),
                jnp.zeros((n_static,), jnp.int32)])
            fresh_tot = jax.lax.psum(sum(new_dc[p] for p in s_preds), axis)
            bad = jnp.any(ovf_vec > 0)

            def keep(old, new):
                return _select_state(bad, old, new)

            return (keep(w_datas, tuple(new_w[p] for p in s_preds)),
                    keep(w_counts, tuple(new_wc[p] for p in s_preds)),
                    keep(d_datas, tuple(new_d[p] for p in s_preds)),
                    keep(d_counts, tuple(new_dc[p] for p in s_preds)),
                    keep(carried_blks, tuple(new_carried)),
                    rounds + jnp.where(bad, 0, 1),
                    trg + jnp.where(bad, 0, jax.lax.psum(triggers, axis)),
                    drv + jnp.where(bad, 0, fresh_tot),
                    jnp.where(bad, live, fresh_tot),
                    ovf_vec)

        def cond(state):
            rounds, live, ovf_vec = state[5], state[8], state[9]
            ok = jnp.logical_not(jnp.any(ovf_vec > 0))
            return jnp.logical_and(jnp.logical_and(live > 0, ok),
                                   rounds < max_rounds)

        def varying(x):
            # the body derives tails and their counts from shard-local
            # rows, so the carry must start varying over the mesh axis
            return jax.lax.pcast(x, axis, to="varying")

        state = (
            tuple(varying(jnp.full((tail_caps[p], base[p].shape[1]),
                                   pad_of(base[p]), base[p].dtype))
                  for p in s_preds),
            tuple(varying(jnp.zeros((), jnp.int32)) for _ in s_preds),
            tuple(deltas0[p] for p in s_preds),
            d_counts0,
            tuple(carried0),
            rounds0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            live0, ovf0)
        (w_datas, w_counts, d_datas, d_counts, _c, rounds, trg, drv,
         _live, ovf_vec) = jax.lax.while_loop(cond, body, state)
        return (w_datas, tuple(c.reshape(1) for c in w_counts),
                d_datas, tuple(c.reshape(1) for c in d_counts),
                rounds, trg, drv, ovf_vec)

    in_specs = (tuple(P(axis, None) for _ in s_preds),
                tuple(P(axis, None) for _ in s_preds),
                tuple(P(axis, None) for _ in o_preds),
                P())
    out_specs = (tuple(P(axis, None) for _ in s_preds),
                 tuple(P(axis) for _ in s_preds),
                 tuple(P(axis, None) for _ in s_preds),
                 tuple(P(axis) for _ in s_preds),
                 P(), P(), P(), P())
    return (jax.jit(jax.shard_map(tg_dist_fixpoint, mesh=mesh,
                                  in_specs=in_specs, out_specs=out_specs)),
            ovf_labels)


# ---------------------------------------------------------------------------
# sharded store (host-side bookkeeping around the device arrays)
# ---------------------------------------------------------------------------
class ShardedKB:
    """Hash-partitioned store: per predicate, a global (ndev * store_cap,
    ar) device array partitioned over the mesh axis (shard = tuple-hash %
    ndev; each shard's valid rows lexsorted) plus per-shard fill counts on
    the host.  ``fit`` re-pads every shard when the planner doubles a store
    capacity (retry path only — steady-state rounds reuse the arrays the
    previous round produced)."""

    def __init__(self, kb, preds, ndev):
        self.ndev = ndev
        self.arity = {p: kb.rels[p].arity for p in preds}
        self.dtype = {p: np.dtype(kb.rels[p].dtype) for p in preds}
        self.data = {}               # pred -> device/np (ndev*cap, ar)
        self.counts = {}             # pred -> np (ndev,) int32
        self.per_shard_max = {}
        for p in preds:
            rows = np.asarray(kb.rels[p].np_rows())
            if rows.size:
                rows = np.unique(rows, axis=0)   # set semantics on entry
            tgt = (np_tuple_hash(rows) % np.uint32(ndev)).astype(np.int64) \
                if len(rows) else np.zeros(0, np.int64)
            parts = []
            for d in range(ndev):
                part = rows[tgt == d]
                if len(part):
                    part = part[np.lexsort(part.T[::-1])]
                parts.append(part)
            self.counts[p] = np.array([len(pt) for pt in parts], np.int32)
            self.per_shard_max[p] = int(self.counts[p].max(initial=0))
            self.data[p] = parts     # packed once planner caps exist

    def pack(self, caps):
        """Materialize the per-shard blocks at the planner's store caps."""
        for p, parts in self.data.items():
            cap = caps.store[p]
            out = np.full((self.ndev, cap, self.arity[p]),
                          pad_value(self.dtype[p]), self.dtype[p])
            for d, part in enumerate(parts):
                out[d, :len(part)] = part
            self.data[p] = out.reshape(self.ndev * cap, self.arity[p])

    def fit(self, pred, cap):
        """Current store block re-padded per shard to ``cap`` rows."""
        data = self.data[pred]
        cur = data.shape[0] // self.ndev
        if cur == cap:
            return data
        return refit_shards(data, self.ndev, cap)

    def to_relations(self, kb):
        """Fold the shards back into lexsorted single-device Relations."""
        for p in self.data:
            ar = self.arity[p]
            blocks = np.asarray(self.data[p]).reshape(self.ndev, -1, ar)
            parts = [blocks[d, :int(self.counts[p][d])]
                     for d in range(self.ndev)]
            rows = (np.concatenate(parts) if parts
                    else np.zeros((0, ar), self.dtype[p]))
            if len(rows):
                rows = rows[np.lexsort(rows.T[::-1])]
            kb.rels[p] = Relation.from_numpy(rows, sorted_by=lex_order(ar))


def refit_shards(data, ndev, new_cap):
    """Re-pad a (ndev * old_cap, ar) blocked array to (ndev * new_cap, ar)
    per shard (capacities only grow, so no valid row is ever sliced off)."""
    arr = np.asarray(data)
    ar = arr.shape[-1]
    arr = arr.reshape(ndev, -1, ar)
    old = arr.shape[1]
    out = np.full((ndev, new_cap, ar), pad_value(arr.dtype), arr.dtype)
    out[:, :min(old, new_cap)] = arr[:, :min(old, new_cap)]
    return out.reshape(ndev * new_cap, ar)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def materialize_distributed(kb, mode: str = "tg", max_rounds: int = 10_000,
                            mesh=None, axis: tuple = ("data",),
                            cfg: DistConfig | None = None,
                            spill: bool = True):
    """Sharded materialization of ``kb`` over ``mesh`` (default: every
    local device on the "data" axis).  ``cfg``, when given, floors the
    planner's per-shard store / delta / exchange-bucket capacities (callers
    that know the instance scale skip the cold-start overflow retries).
    Returns MatStats, or None when the program is outside the plannable
    fragment (the caller falls back to the fused / two-phase executors).

    Capacity overflows retry under a ``RetryBudget``; an exhausted budget
    mid-run ``spill``s the remaining rounds to the two-phase executor
    (``spill=False`` re-raises the ``CapacityError``).

    With ``REPRO_CKPT_DIR`` set, every shard's trimmed store and delta
    rows are checkpointed at round / fixpoint-exit boundaries under one
    coordinator manifest, and the driver restores ELASTICALLY: the
    checkpointed rows are executor- and mesh-neutral, so a run saved at
    one ndev resumes at any other — the restored facts simply re-partition
    through the same full-tuple-hash canonical home the exchanges use."""
    from repro.engine.materialize import MatStats
    if mode not in ("tg", "tg_noopt"):
        return None
    program = kb.program
    plans = {}
    for rule in program.rules:
        plan = compile_rule_plan(rule, kb.dict)
        if plan is None:
            return None
        plans[id(rule)] = plan

    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh()
    ndev = axis_size(mesh, axis)
    preds = tuple(sorted(kb.rels))
    use_prefilter = mode == "tg"
    st = MatStats(mode=mode)
    st.extra.update(dist=True, ndev=ndev)

    # restore BEFORE sharding: maybe_resume rebuilds kb.rels as global
    # host relations, and the ShardedKB constructor below re-partitions
    # them by tuple hash for THIS mesh — that is the whole elastic story
    ck = recovery.EngineCheckpointer(kb, mode, "dist")
    resume = ck.maybe_resume(st)

    skb = ShardedKB(kb, preds, ndev)
    fp = (program_fingerprint((plans[id(r)].key for r in program.rules),
                              sum(kb.rels[p].count for p in preds)),
          "dist", ndev)
    caps = _Caps(fp, {p: (None, skb.per_shard_max[p]) for p in preds},
                 ndev=ndev)
    if ck.caps_state is not None and \
            st.extra.get("resumed_from") == ("dist", ndev):
        # capacity plans are per-shard: only a same-shape dist run's plan
        # transfers; any other source just replans (and re-converges)
        caps.adopt(ck.caps_state)
    if cfg is not None:
        for p in preds:
            caps.store[p] = max(caps.store[p], cfg.shard_cap)
        caps._delta_guess = max(caps._delta_guess, cfg.delta_cap)
        caps._bucket_guess = max(caps._bucket_guess, cfg.bucket_cap)
    skb.pack(caps)

    row_bytes = max((skb.dtype[p].itemsize * skb.arity[p] for p in preds),
                    default=8)
    budget = RetryBudget(caps, row_bytes=row_bytes)

    deltas: dict = {}    # pred -> device (ndev*delta_cap, ar), PAD-padded

    def state_fn():
        """Per-shard checkpoint payloads: each shard's trimmed store rows
        and PAD-filtered delta rows; the base facts ride shard 0."""
        shards = [{} for _ in range(ndev)]
        for p in preds:
            ar = skb.arity[p]
            blocks = np.asarray(skb.data[p]).reshape(ndev, -1, ar)
            for s in range(ndev):
                shards[s][f"store__{p}"] = blocks[s, :int(skb.counts[p][s])]
        for p, d in deltas.items():
            ar = skb.arity[p]
            pad = pad_value(skb.dtype[p])
            blocks = np.asarray(d).reshape(ndev, -1, ar)
            for s in range(ndev):
                rows = blocks[s][blocks[s, :, 0] != pad]
                if len(rows):
                    rows = rows[np.lexsort(rows.T[::-1])]
                shards[s][f"delta__{p}"] = rows
        for p, rel in kb.base.items():
            shards[0][f"base__{p}"] = rel.np_rows()
        return shards

    def fit_delta(pred):
        data = deltas[pred]
        cap = caps.delta_cap(pred)
        if data.shape[0] // ndev == cap:
            return data
        return refit_shards(data, ndev, cap)

    def run_round(active, delta_preds, is_ext=False):
        prefilter = use_prefilter and not is_ext   # no Def. 23 in round 1
        while True:
            with TraceAnnotation("tg.round", round=st.rounds):
                sig = _dist_signature(mesh, axis, ndev, preds, caps, active,
                                      delta_preds, prefilter)
                fn, ovf_labels, derived = _cached_program(
                    sig, lambda: _build_dist_round(mesh, axis, ndev, preds,
                                                   caps, active, delta_preds,
                                                   prefilter))
                out = fn(tuple(skb.fit(p, caps.store[p]) for p in preds),
                         tuple(jnp.asarray(skb.counts[p]) for p in preds),
                         tuple(fit_delta(p) for p in delta_preds))
                n_stores, n_counts, n_deltas, n_dcounts, fresh, trg, ovf = out
                # ONE blocking pull per round attempt, independent of ndev:
                # counts + fresh totals + triggers + the overflow vector
                with TraceAnnotation("tg.pull", site="dist"):
                    pulled = jax.device_get((n_counts, fresh, trg, ovf))
                ops.HOST_SYNC_STATS.dist_pulls += 1
            cnts, fresh, trg, ovf = pulled
            if not ovf.any():
                budget.ok()
                for p, d, c in zip(preds, n_stores, cnts):
                    skb.data[p] = d
                    skb.counts[p] = np.asarray(c, np.int32)
                st.triggers += int(trg)
                new = {}
                for p, d, ft in zip(derived, n_deltas, fresh):
                    st.derived += int(ft)
                    if int(ft):
                        new[p] = d
                return new
            ops.HOST_SYNC_STATS.dist_retries += 1
            # a rule active at several delta positions repeats its labels;
            # dedupe so a shared capacity doubles once per retry
            budget.overflow(dict.fromkeys(
                l for f, l in zip(ovf, ovf_labels) if f))

    def fit_delta_fix(pred):
        """Delta block for the fixpoint program: the live delta refit to
        the planner cap, or an all-PAD block for quiescent S-preds."""
        if pred not in deltas:
            cap = caps.delta_cap(pred)
            return np.full((ndev * cap, skb.arity[pred]),
                           pad_value(skb.dtype[pred]), skb.dtype[pred])
        return fit_delta(pred)

    def fold_tails(s_preds_, w_datas, wcnts):
        """Fold the per-shard fixpoint tails into the sharded store on the
        host (the rare exit path): concat + lexsort per shard, growing a
        store capacity when a shard fills.  Tail rows were deduped against
        store | tail on their canonical-home shard, so this is a pure
        union of disjoint sorted sets."""
        for p, d, cnts in zip(s_preds_, w_datas, wcnts):
            cnts = np.asarray(cnts, np.int64)
            if not cnts.sum():
                continue
            ar = skb.arity[p]
            tail_blk = np.asarray(d).reshape(ndev, -1, ar)
            store_blk = np.asarray(skb.data[p]).reshape(ndev, -1, ar)
            parts = []
            for s in range(ndev):
                rows = np.concatenate(
                    [store_blk[s, :int(skb.counts[p][s])],
                     tail_blk[s, :int(cnts[s])]])
                if len(rows):
                    rows = rows[np.lexsort(rows.T[::-1])]
                parts.append(rows)
            new_counts = np.array([len(pt) for pt in parts], np.int32)
            cap = caps.store[p]
            while cap < new_counts.max(initial=0):
                cap *= 2
            caps.store[p] = cap
            out = np.full((ndev, cap, ar), pad_value(skb.dtype[p]),
                          skb.dtype[p])
            for s, pt in enumerate(parts):
                out[s, :len(pt)] = pt
            skb.data[p] = out.reshape(ndev * cap, ar)
            skb.counts[p] = new_counts

    def run_fixpoint(live):
        """Finish a linear fixpoint phase inside the while_loop program:
        one host pull per program EXIT (converged / tail fold / capacity
        retry), not per round.  Returns True when the phase ran; False
        when the remaining program is not linear (the caller steps one
        host-driven round instead)."""
        nonlocal deltas
        tail = _linear_tail(int_plans, live)
        if tail is None:
            return False
        s_preds_, active = tail
        o_preds_ = tuple(p for p in preds if p not in s_preds_)
        while True:
            with TraceAnnotation("tg.fixpoint", round=st.rounds):
                sig = _dist_fix_signature(mesh, axis, ndev, s_preds_,
                                          o_preds_, caps, active,
                                          use_prefilter, max_rounds)
                fn, ovf_labels = _cached_program(
                    sig, lambda: _build_dist_fixpoint(
                        mesh, axis, ndev, s_preds_, o_preds_, caps, active,
                        use_prefilter, max_rounds))
                out = fn(tuple(skb.fit(p, caps.store[p]) for p in s_preds_),
                         tuple(fit_delta_fix(p) for p in s_preds_),
                         tuple(skb.fit(p, caps.store[p]) for p in o_preds_),
                         jnp.int32(st.rounds))
                w_datas, w_counts, d_datas, d_counts, rounds, trg, drv, \
                    ovf = out
                # ONE blocking pull per fixpoint-program exit: tail + delta
                # counts, the loop's round/trigger/derived totals, and the
                # overflow vector
                with TraceAnnotation("tg.pull", site="dist"):
                    pulled = jax.device_get((w_counts, d_counts, rounds, trg,
                                             drv, ovf))
                ops.HOST_SYNC_STATS.dist_pulls += 1
                ops.HOST_SYNC_STATS.dist_fixpoint_pulls += 1
            wcnts, dcnts, rounds, trg, drv, ovf = pulled
            ops.HOST_SYNC_STATS.dist_fixpoint_iters += \
                int(rounds) - st.rounds
            prev_rounds = st.rounds
            st.rounds = int(rounds)
            st.triggers += int(trg)
            st.derived += int(drv)
            deltas = {p: d for p, d, c in zip(s_preds_, d_datas, dcnts)
                      if int(np.asarray(c).sum())}
            with TraceAnnotation("tg.fold"):
                fold_tails(s_preds_, w_datas, wcnts)
            if st.rounds > prev_rounds:
                budget.ok()     # the loop advanced: real progress
                progressed[0] = True
            ck.boundary(st, state_fn, caps=caps)
            if not ovf.any():
                return True
            # tail-full exits included: the fold above made room, but
            # without growth a long phase would exit every tail_cap-ish
            # rounds and pulls would scale with the fact count.  Doubling
            # geometrically bounds tail exits at O(log facts) cold and —
            # via the capacity memo — ONE pull per phase warm.
            budget.overflow(dict.fromkeys(
                l for f, l in zip(ovf, ovf_labels) if f))

    progressed = [resume is not None]

    def drive():
        nonlocal deltas
        if resume is not None:
            st.extra["resumed"] = True
            for p, rows in resume.items():
                ar = skb.arity[p]
                tgt = (np_tuple_hash(rows)
                       % np.uint32(ndev)).astype(np.int64)
                parts = []
                for d in range(ndev):
                    part = rows[tgt == d]
                    if len(part):
                        part = part[np.lexsort(part.T[::-1])]
                    parts.append(part)
                caps.seed_delta(p, max(len(pt) for pt in parts))
                cap = caps.delta_cap(p)
                blk = np.full((ndev, cap, ar), pad_value(skb.dtype[p]),
                              skb.dtype[p])
                for d, part in enumerate(parts):
                    blk[d, :len(part)] = part
                deltas[p] = blk.reshape(ndev * cap, ar)
        else:
            # round 1: extensional rules over B
            ext_active = tuple((plans[id(r)], None)
                               for r in program.extensional_rules())
            if ext_active:
                deltas = run_round(ext_active, (), is_ext=True)
            st.rounds = 1
            progressed[0] = True
            ck.boundary(st, state_fn, caps=caps)

        # fixpoint rounds: whole linear phases run inside the compiled
        # while_loop program (one pull per phase exit); non-linear
        # stretches fall back to host-stepped rounds (one compiled program
        # + one scalar pull per round, psum convergence)
        fixpoint_on = ops.dist_fixpoint_enabled()
        while deltas and st.rounds < max_rounds:
            live = tuple(sorted(deltas))
            if fixpoint_on and run_fixpoint(live):
                continue
            active = tuple((plans[id(r)], j) for r in int_rules
                           for j, a in enumerate(r.body)
                           if a.pred in deltas)
            if not active:
                break
            deltas = run_round(active, live)
            st.rounds += 1
            progressed[0] = True
            ck.boundary(st, state_fn, caps=caps)

    int_rules = program.intensional_rules()
    int_plans = [plans[id(r)] for r in int_rules]
    try:
        drive()
    except CapacityError as e:
        if not spill:
            raise
        if not progressed[0]:
            return None     # cold-start overflow: plain fragment fallback
        # graceful degradation: gather the last-good shards back into the
        # kb and run the remaining rounds on the two-phase executor
        from repro.engine.materialize import _fixpoint_rounds
        skb.to_relations(kb)
        seed = {}
        for p, d in deltas.items():
            ar = skb.arity[p]
            pad = pad_value(skb.dtype[p])
            blk = np.asarray(d).reshape(ndev, -1, ar)
            rows = blk.reshape(-1, ar)
            rows = rows[rows[:, 0] != pad]
            if len(rows):
                rows = rows[np.lexsort(rows.T[::-1])]
            seed[p] = Relation.from_numpy(np.ascontiguousarray(rows),
                                          sorted_by=lex_order(ar))
        st.extra["spilled"] = str(e)
        _fixpoint_rounds(kb, st, seed, mode, max_rounds, ck=ck)
        return st

    skb.to_relations(kb)
    caps.memoize()
    ck.final(st, state_fn, caps=caps)
    return st


# ---------------------------------------------------------------------------
# back-compat TC entries (the hand-written TC step this module used to ship
# is gone: TC is now just one more Datalog program over the general executor)
# ---------------------------------------------------------------------------
def _tc_program():
    from repro.core.terms import parse_program
    return parse_program("""
        e(X, Y) -> T(X, Y)
        T(X, Y) & e(Y, Z) -> T(X, Z)
    """)


def run_distributed_tc(edges: np.ndarray, mesh,
                       cfg: DistConfig = DistConfig()):
    """Transitive closure of int (n, 2) ``edges`` over the general sharded
    executor; ``cfg``'s capacities floor the planner's.  Returns
    (t_rows (m, 2) int np, count, triggers, rounds)."""
    from repro.core.terms import Atom
    from repro.engine.materialize import EngineKB
    B = [Atom("e", (f"n{int(a)}", f"n{int(b)}")) for a, b in edges]
    kb = EngineKB(_tc_program(), B)
    st = materialize_distributed(kb, mode="tg", max_rounds=cfg.max_rounds,
                                 mesh=mesh, axis=cfg.axis, cfg=cfg)
    rows = np.array(sorted(
        tuple(int(t[1:]) for t in atom.args)
        for atom in kb.decode_facts() if atom.pred == "T"), np.int32)
    return rows, len(rows), st.triggers, st.rounds


def lower_distributed_tc(mesh, cfg: DistConfig = DistConfig()):
    """Dry-run entry: lower one compiled TG round of the TC program (delta
    exchange + planned join + canonical-home absorb) at the configured
    per-shard capacities on a target mesh."""
    from repro.engine.dictionary import Dictionary
    ndev = axis_size(mesh, cfg.axis)
    program = _tc_program()
    dic = Dictionary()
    plans = [compile_rule_plan(r, dic) for r in program.rules]
    preds = ("T", "e")
    caps = _Caps(("dryrun", ndev), {p: (None, 1) for p in preds}, ndev=ndev)
    active = ((plans[1], 0),)                    # T-delta in body position 0
    derived = ("T",)
    labels = _round_ovf_labels(active, True, derived)
    for p in preds:
        caps.store[p] = cfg.shard_cap
    caps.delta["T"] = cfg.delta_cap
    caps.join[(plans[1].key, 0)] = cfg.delta_cap * 4
    for key in _bucket_keys(labels):
        caps.bucket[key] = cfg.bucket_cap
    fn, _, _ = _build_dist_round(mesh, cfg.axis, ndev, preds, caps, active,
                                 ("T",), True)
    s32 = jnp.int32
    store_specs = tuple(jax.ShapeDtypeStruct((ndev * cfg.shard_cap, 2), s32)
                        for _ in preds)
    count_specs = tuple(jax.ShapeDtypeStruct((ndev,), s32) for _ in preds)
    delta_specs = (jax.ShapeDtypeStruct((ndev * cfg.delta_cap, 2), s32),)
    return fn.lower(store_specs, count_specs, delta_specs)
