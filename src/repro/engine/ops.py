"""Vectorized relational operators on padded narrow-dtype relations.

Rows carry the store dtype (``REPRO_STORE_DTYPE``: int16/int32/int64 —
see ``repro.engine.relation``); every core reads its PAD sentinel and key
widths off the input arrays, so one set of traced functions serves all
store widths (jit retraces per dtype via its aval cache).

Execution contracts
-------------------
Every primitive exists in two layers:

* **Traceable cores** (``*_core`` functions): pure, shape-stable jnp
  functions with no host interaction — callable inside any jitted program
  (the fused round executor in ``repro.engine.fused``, the ``shard_map``
  bodies in ``repro.engine.distributed``, or the two-phase wrappers below).
  Cores never choose capacities; output capacities are arguments.
* **Two-phase host wrappers** (``dedup``/``filter_rows``/``sm_join``/
  ``antijoin``/...): the host-facing API over ``Relation`` values.  Data-
  dependent sizes follow the two-phase pattern: a jitted *count* pass, a
  blocking device->host pull of the count (recorded in ``HOST_SYNC_STATS``),
  a host pow-2 bucket choice, then a jitted *materialize* pass.

``REPRO_FUSED=1`` makes ``materialize()`` route whole rounds (and, for
linear-tail fixpoints, the whole fixpoint via ``lax.while_loop``) through one
compiled XLA program built from the cores — see ``repro.engine.fused`` for
the capacity-planner / overflow-doubling contract.  The wrappers here remain
the reference path (``REPRO_FUSED=0``) and the fallback for programs the
fused planner does not cover (existential rules).

Sortedness invariant
--------------------
Operators honor the ``Relation.sorted_by`` marker: ``dedup``/``antijoin``/
``sm_join`` skip their sort pass when an input already carries the needed
order, and ``merge_union`` folds a small sorted delta into a sorted store
with two lexicographic binary-search passes instead of a concat-and-resort
(O((m+n)·ar·log) vs O((m+n)·log(m+n)) full sort work per call — and, more
importantly, no re-sorting of the already-sorted store).  ``SORT_STATS``
counts performed vs skipped sort passes; ``REPRO_SORTED_STORE=0`` disables
the fast paths for A/B benchmarking.

Kernel dispatch
---------------
Setting ``REPRO_USE_PALLAS=1`` routes the single-key sort and unique-mask
inner loops through the Pallas kernels in ``repro.kernels.ops``
(``sort_with_payload``, ``unique_mask``; interpret mode on the CPU backend,
compiled on TPU).  The jnp implementations here are the reference path and
the default.  Multi-column lexsorts, membership probes and the merge-union
binary searches stay on the jnp path in both modes.

Tracing
-------
Each core runs under a ``jax.named_scope`` (``tg.sort``, ``tg.join``,
``tg.probe``, ``tg.merge``, ``tg.compact``), so every device op it emits
carries the scope in its ``op_name`` metadata; where scopes nest, the
outermost one names the op (a probe inside ``merge_core`` is merge work).
Every blocking count pull runs inside a ``tg.pull`` host span
(``jax.profiler.TraceAnnotation``) beside its ``HOST_SYNC_STATS``
increment.  Neither costs anything unless a profiler runs.

Env-flag matrix
---------------
=================== ======= ====================================================
``REPRO_USE_PALLAS`` ``0``   Pallas kernels for sort/unique inner loops
``REPRO_SORTED_STORE`` ``1`` sortedness markers + incremental merge-union
``REPRO_FUSED``      ``0``   fused round executor (one XLA program per round)
``REPRO_DIST``       ``0``   sharded shard_map executor over all local devices
``REPRO_DIST_FIXPOINT`` ``1`` linear-tail while_loop fixpoint inside shard_map
=================== ======= ====================================================
"""
from __future__ import annotations

import os
from dataclasses import dataclass, replace
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.engine.relation import (PAD, Relation, lex_order, next_pow2,
                                   pad_of)


# ---------------------------------------------------------------------------
# dispatch switches + sort-pass / host-sync accounting
# ---------------------------------------------------------------------------
def use_pallas() -> bool:
    """Route sort/unique inner loops through the Pallas kernels."""
    return os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def sorted_store_enabled() -> bool:
    """Honor ``sorted_by`` markers (skip redundant sorts, merge unions)."""
    return os.environ.get("REPRO_SORTED_STORE", "1") != "0"


def fused_enabled() -> bool:
    """Route eligible materialization rounds through the fused executor."""
    return os.environ.get("REPRO_FUSED", "0") == "1"


def dist_enabled() -> bool:
    """Route eligible materialization through the sharded (shard_map)
    executor over every local device (``materialize(backend="dist")``)."""
    return os.environ.get("REPRO_DIST", "0") == "1"


def dist_fixpoint_enabled() -> bool:
    """Run linear-tail fixpoint phases of the distributed executor inside
    one ``lax.while_loop``-under-``shard_map`` program (on by default;
    ``REPRO_DIST_FIXPOINT=0`` forces the host-stepped per-round path for
    A/B comparison)."""
    return os.environ.get("REPRO_DIST_FIXPOINT", "1") != "0"


_KERNELS = None


def _kernels():
    global _KERNELS
    if _KERNELS is None:
        from repro.kernels import ops as _ko
        _KERNELS = _ko
    return _KERNELS


@dataclass
class SortStats:
    """Python-level calls on the two-phase host path: sorts run by
    ``lexsort_rows`` / ``sort_by``, sorts they skipped through a
    ``sorted_by`` marker, and ``merge_union`` / ``merge_diff`` merges.  Read
    by ``tests/test_sorted_store.py`` and ``benchmarks/``.  It is not a count
    of the sorts in the fused programs, whose cores run on the device unseen
    by these counters: there the device trace's time under the ``tg.sort``
    scope is the measure."""
    lexsort: int = 0       # full row lexsorts executed
    key_sort: int = 0      # single-key sorts executed (sm_join inputs)
    merges: int = 0        # incremental merge-unions executed
    skipped: int = 0       # sort passes avoided via a sorted_by marker

    def reset(self):
        self.lexsort = self.key_sort = self.merges = self.skipped = 0

    def total_sorts(self) -> int:
        return self.lexsort + self.key_sort


SORT_STATS = SortStats()


@dataclass
class HostSyncStats:
    """Blocking device->host synchronization points.

    Each two-phase wrapper pulls its count-pass result to the host before it
    can pick an output bucket (``count_pulls`` — one per primitive call).
    The fused executor pulls once per compiled round / fixpoint attempt
    (``fused_pulls``), the distributed executor once per sharded round
    attempt regardless of the shard count (``dist_pulls``, the TOTAL pull
    count including fixpoint-program exits); both count capacity-overflow
    recompile-and-retry events (``fused_retries`` / ``dist_retries`` —
    host-stepped round retries only; fixpoint-phase capacity retries are
    visible as extra ``dist_fixpoint_pulls`` instead, so retried rounds
    and fixpoint-phase exits stay distinguishable).

    The distributed while_loop fixpoint adds two counters:
    ``dist_fixpoint_pulls`` — pulls taken at fixpoint-program exits
    (convergence, tail-full fold-and-re-enter, or capacity retry; each is
    also counted in ``dist_pulls``) — and ``dist_fixpoint_iters`` — rounds
    executed on-device inside the loop with NO host pull.  The accounting
    invariant the tests assert:

        dist_pulls == (rounds - dist_fixpoint_iters)   # host-stepped rounds
                      + dist_retries                    # round retries
                      + dist_fixpoint_pulls             # fixpoint exits

    ``total()`` is the engine's host-sync work metric, reported next to
    trigger counts by the benchmarks."""
    count_pulls: int = 0
    fused_pulls: int = 0
    fused_retries: int = 0
    dist_pulls: int = 0
    dist_retries: int = 0
    dist_fixpoint_pulls: int = 0
    dist_fixpoint_iters: int = 0

    def reset(self):
        self.count_pulls = self.fused_pulls = self.fused_retries = 0
        self.dist_pulls = self.dist_retries = 0
        self.dist_fixpoint_pulls = self.dist_fixpoint_iters = 0

    def snapshot(self) -> "HostSyncStats":
        """Immutable copy of the current counters — callers comparing
        before/after an operation (e.g. the mid-run-restore invariant
        tests) hold a snapshot instead of racing the live singleton."""
        return replace(self)

    def total(self) -> int:
        return self.count_pulls + self.fused_pulls + self.dist_pulls


HOST_SYNC_STATS = HostSyncStats()


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _kernel_width(data) -> bool:
    """The Pallas kernels take 32-bit words: int64 stores stay on jnp."""
    return data.dtype.itemsize <= 4


# ===========================================================================
# traceable cores — pure jnp, shape-stable, no host interaction.  Safe to
# call inside jit / while_loop / shard_map; static args (column indices,
# capacities, pallas routing) must be python values at trace time.
# ===========================================================================
@jax.named_scope("tg.sort")
def lexsort_core(data, pallas: bool | None = None):
    """Full-row lexicographic sort of a padded (cap, ar) block (PAD rows
    sort last).  Single-column blocks route through the Pallas sort kernel
    when ``pallas`` (pow-2 caps only)."""
    cap, ar = data.shape
    if pallas is None:
        pallas = use_pallas()
    if pallas and ar == 1 and _is_pow2(cap):
        return keysort_core(data, 0, pallas=True)
    key = scalar_key(data)
    if key is not None:
        return data[jnp.argsort(key)]
    keys = tuple(data[:, c] for c in reversed(range(ar)))
    return data[jnp.lexsort(keys)]


@jax.named_scope("tg.sort")
def keysort_core(data, key_col: int, pallas: bool | None = None):
    """Sort rows of a padded block by one key column."""
    cap = data.shape[0]
    if pallas is None:
        pallas = use_pallas()
    if pallas and _is_pow2(cap) and _kernel_width(data):
        K = _kernels()
        vals = jnp.arange(cap, dtype=jnp.int32)
        _, perm = K.sort_with_payload(data[:, key_col], vals,
                                      tile=min(1024, cap))
        return data[perm]
    return data[jnp.argsort(data[:, key_col])]


@jax.named_scope("tg.compact")
def dedup_mask_core(sorted_data, pallas: bool | None = None):
    """First-occurrence mask over lexsorted rows (PAD rows excluded)."""
    if pallas is None:
        pallas = use_pallas()
    if pallas and _kernel_width(sorted_data):
        K = _kernels()
        return K.unique_mask(sorted_data).astype(bool)
    prev = jnp.roll(sorted_data, 1, axis=0)
    neq = jnp.any(sorted_data != prev, axis=1)
    neq = neq.at[0].set(True)
    valid = sorted_data[:, 0] != pad_of(sorted_data)
    return jnp.logical_and(neq, valid)


@jax.named_scope("tg.compact")
def filter_mask_core(data, eq_pairs=(), const_pairs=()):
    """Row-selection mask: valid rows meeting column-equality (repeated
    vars) and column-constant constraints."""
    valid = data[:, 0] != pad_of(data)
    for a, b in eq_pairs:
        valid &= data[:, a] == data[:, b]
    for c, v in const_pairs:
        valid &= data[:, c] == v
    return valid


@jax.named_scope("tg.compact")
def compact_core(data, mask, out_cap: int):
    """Scatter masked rows to the front of a fresh (out_cap, ar) PAD block,
    preserving their relative order (so sortedness survives compaction).
    Rows beyond ``out_cap`` are dropped — callers detect that via
    ``sum(mask) > out_cap``."""
    pos = jnp.cumsum(mask) - 1
    idx = jnp.where(mask, pos, out_cap)
    out = jnp.full((out_cap + 1, data.shape[1]), pad_of(data), data.dtype)
    out = out.at[idx].set(data, mode="drop")
    return out[:out_cap]


@jax.named_scope("tg.compact")
def project_core(data, cols):
    """Column gather; invalid (PAD) rows stay fully PAD."""
    valid = data[:, 0] != pad_of(data)
    out = data[:, jnp.array(cols, jnp.int32)]
    return jnp.where(valid[:, None], out, pad_of(data))


@jax.named_scope("tg.join")
def join_count_core(ldata, rdata_sorted, lkey: int, rkey: int):
    """Count pass of the sort-merge join: per-left-row match ranges in the
    right block (sorted by ``rkey``).  Returns (total, per, cum, lo)."""
    lk = ldata[:, lkey]
    rk = rdata_sorted[:, rkey]
    lo = jnp.searchsorted(rk, lk, side="left")
    hi = jnp.searchsorted(rk, lk, side="right")
    per = jnp.where(lk != pad_of(ldata), hi - lo, 0)
    cum = jnp.cumsum(per) - per           # exclusive prefix
    return jnp.sum(per), per, cum, lo


@jax.named_scope("tg.join")
def join_gather_core(ldata, rdata, per, cum, lo, total, out_cap: int):
    """Materialize pass: emit [l cols..., r cols...] rows into a
    (out_cap, lar+rar) block.  Rows past ``out_cap`` are dropped (overflow
    is ``total > out_cap``, checked by the caller)."""
    lcap = ldata.shape[0]
    rcap = rdata.shape[0]
    t = jnp.arange(out_cap)
    # left row for output t: last i with cum[i] <= t
    i = jnp.searchsorted(cum + per, t, side="right")
    i = jnp.clip(i, 0, lcap - 1)
    j = jnp.clip(lo[i] + (t - cum[i]), 0, rcap - 1)
    valid = t < total
    out = jnp.concatenate([ldata[i], rdata[j]], axis=1)
    return jnp.where(valid[:, None], out, pad_of(ldata))


def _range_narrow(col, key, lo, hi):
    """Per-row binary search narrowing [lo,hi) to col==key (col sorted within
    each [lo,hi) range by lexsort invariant).  The step loop is a
    ``fori_loop`` so the traced graph stays small — these searches are built
    per capacity bucket and an unrolled log2(n) body made recompilation the
    dominant cost as the store grows through buckets."""
    n = col.shape[0]
    steps = max(1, int(np.ceil(np.log2(n + 1))))

    def bs(le):
        def body(_, lh):
            l, h = lh
            mid = (l + h) // 2
            v = col[jnp.clip(mid, 0, n - 1)]
            go_right = jnp.where(le, v <= key, v < key)
            in_range = mid < h
            l = jnp.where(jnp.logical_and(in_range, go_right), mid + 1, l)
            h = jnp.where(jnp.logical_and(in_range,
                                          jnp.logical_not(go_right)), mid, h)
            return l, h
        return jax.lax.fori_loop(0, steps, body, (lo, hi))[0]

    return bs(False), bs(True)


def scalar_key(rows):
    """The order-preserving scalar key of arity-1 rows (their one column),
    or None: wider rows sort by all their columns and search column by
    column."""
    if rows.shape[1] == 1:
        return rows[:, 0]
    return None


@jax.named_scope("tg.probe")
def lex_range_core(hay_sorted, probe):
    """Per-probe-row [lo, hi) occurrence range in a lexsorted haystack:
    per-column range narrowing; when a column value is absent the range
    collapses to the insertion point and stays there."""
    # *_like of a probe column: under shard_map the loop carry then starts
    # with the probe's varying-axes type, as the fori_loop body produces
    lo = jnp.zeros_like(probe[:, 0], jnp.int32)
    hi = jnp.full_like(probe[:, 0], hay_sorted.shape[0], jnp.int32)
    for c in range(hay_sorted.shape[1]):
        lo, hi = _range_narrow(hay_sorted[:, c], probe[:, c], lo, hi)
    return lo, hi


def _lex_searchsorted_left(hay, probe):
    """Leftmost insertion positions of each ``probe`` row in lexsorted
    ``hay``."""
    hk = scalar_key(hay)
    if hk is not None:
        return jnp.searchsorted(hk, scalar_key(probe), side="left"
                                ).astype(jnp.int32)
    return lex_range_core(hay, probe)[0]


def _lex_searchsorted_right(hay, probe):
    """Rightmost insertion positions of each ``probe`` row in lexsorted
    ``hay``."""
    hk = scalar_key(hay)
    if hk is not None:
        return jnp.searchsorted(hk, scalar_key(probe), side="right"
                                ).astype(jnp.int32)
    return lex_range_core(hay, probe)[1]


@jax.named_scope("tg.probe")
def member_mask_core(probe_rows, hay_sorted):
    """Row membership of each probe row in a lexsorted haystack (PAD probe
    rows report non-member: PAD columns never match valid haystack rows and
    match only haystack PAD padding, which is excluded either way)."""
    valid = probe_rows[:, 0] != pad_of(probe_rows)
    hk = scalar_key(hay_sorted)
    if hk is not None:
        pk = scalar_key(probe_rows)
        n = hk.shape[0]
        idx = jnp.searchsorted(hk, pk).astype(jnp.int32)
        found = hk[jnp.clip(idx, 0, n - 1)] == pk
        found = jnp.logical_and(found, idx < n)
        return jnp.logical_and(found, valid)
    lo, hi = lex_range_core(hay_sorted, probe_rows)
    return jnp.logical_and(hi > lo, valid)


@jax.named_scope("tg.probe")
def anti_keep_core(data, hay_sorted, cols):
    """Keep-mask for the antijoin: valid rows of ``data`` whose ``cols``
    tuple does NOT occur in the lexsorted haystack."""
    valid = data[:, 0] != pad_of(data)
    found = member_mask_core(project_core(data, cols), hay_sorted)
    return jnp.logical_and(valid, jnp.logical_not(found))


@jax.named_scope("tg.merge")
def merge_diff_core(A, B_sorted, out_cap: int):
    """Sorted set-difference: rows of block A (lexsorted) minus rows of
    lexsorted block B, compacted into a fresh (out_cap, ar) PAD block.
    Mirrors ``merge_core``'s binary-search discipline — every A row is one
    lexicographic membership probe into B, no sort pass — and preserves A's
    order (compaction keeps relative order).  Returns (out, n_kept); overflow
    is ``n_kept > out_cap``, checked by the caller."""
    keep = anti_keep_core(A, B_sorted, tuple(range(A.shape[1])))
    n = jnp.sum(keep).astype(jnp.int32)
    return compact_core(A, keep, out_cap), n


@jax.named_scope("tg.merge")
def merge_core(A, B, na, nb):
    """Merge sorted block B (bcap rows, nb valid) into sorted block A
    (out_cap rows, na valid).  Duplicate rows may appear within and across
    the blocks: ties place the A run first (a stable multiset merge), so
    disjoint-set callers (the sorted-store fold) and multiset callers (the
    exchange run merge) share one core.  Only the B side is binary-searched
    — bcap probes, not out_cap — and the A side's shifts are recovered from
    a histogram of the B insertion points + cumsum (O(out_cap) streaming
    work): output slot of B[i] = i + p_i where p_i = #{A lex<= B[i]}, and
    output slot of A[j] = j + #{i : p_i <= j}.  The output capacity is A's;
    overflow is ``na + nb > A.shape[0]``, checked by the caller."""
    out_cap, ar = A.shape
    bcap = B.shape[0]
    ia = jnp.arange(out_cap, dtype=jnp.int32)
    ib = jnp.arange(bcap, dtype=jnp.int32)
    valid_b = ib < nb
    # insertion position of each B row AFTER any equal A rows; PAD rows are
    # lex-max so p only counts valid A rows
    p = _lex_searchsorted_right(A, B)
    h = jnp.zeros(out_cap + 1, jnp.int32)
    h = h.at[jnp.where(valid_b, p, out_cap)].add(1, mode="drop")
    cnt = jnp.cumsum(h)[:out_cap]            # #{valid B rows lex< A[j]}
    pos_a = jnp.where(ia < na, ia + cnt, out_cap)
    pos_b = jnp.where(valid_b, ib + p, out_cap)
    out = jnp.full((out_cap, ar), pad_of(A), A.dtype)
    out = out.at[pos_a].set(A, mode="drop")
    out = out.at[pos_b].set(B, mode="drop")
    return out


# ===========================================================================
# two-phase host wrappers over the cores
# ===========================================================================
# ---------------------------------------------------------------------------
# sorting / dedup
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def _lexsort_fn(cap, ar, pallas):
    @jax.jit
    def tg_lexsort(data):
        return lexsort_core(data, pallas=pallas)
    return tg_lexsort


def lexsort_rows(rel: Relation) -> Relation:
    order = lex_order(rel.arity)
    if sorted_store_enabled() and rel.sorted_by == order:
        SORT_STATS.skipped += 1
        return rel
    data = _lexsort_fn(rel.capacity, rel.arity, use_pallas())(rel.data)
    SORT_STATS.lexsort += 1
    return Relation(data, rel.count, order)


@lru_cache(maxsize=None)
def _dedup_count_fn(cap, ar, pallas):
    @jax.jit
    def tg_dedup_count(sorted_data):
        mask = dedup_mask_core(sorted_data, pallas=pallas)
        return jnp.sum(mask), mask
    return tg_dedup_count


@lru_cache(maxsize=None)
def _compact_fn(cap, ar, out_cap):
    @jax.jit
    def tg_compact(data, mask):
        return compact_core(data, mask, out_cap)
    return tg_compact


def dedup(rel: Relation) -> Relation:
    """Sort (skipped on a lexsorted input) + adjacent-unique + compact.
    Output is lexsorted and marked."""
    if rel.count == 0:
        return Relation.empty(rel.arity, dtype=rel.dtype)
    s = lexsort_rows(rel)
    n, mask = _dedup_count_fn(s.capacity, s.arity, use_pallas())(s.data)
    with TraceAnnotation("tg.pull", site="count"):
        n = int(n)
    HOST_SYNC_STATS.count_pulls += 1
    cap = next_pow2(n)
    out = _compact_fn(s.capacity, s.arity, cap)(s.data, mask)
    return Relation(out, n, lex_order(rel.arity))


# ---------------------------------------------------------------------------
# filters / projection
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def _filter_count_fn(cap, ar, eq_pairs, const_pairs):
    @jax.jit
    def tg_filter_count(data):
        valid = filter_mask_core(data, eq_pairs, const_pairs)
        return jnp.sum(valid), valid
    return tg_filter_count


def filter_rows(rel: Relation, eq_pairs=(), const_pairs=()) -> Relation:
    """Select rows with col equality (repeated vars) / constant constraints.
    Compaction keeps row order, so the sortedness marker is preserved."""
    if rel.count == 0 or (not eq_pairs and not const_pairs):
        return rel
    n, mask = _filter_count_fn(rel.capacity, rel.arity, tuple(eq_pairs),
                               tuple(const_pairs))(rel.data)
    with TraceAnnotation("tg.pull", site="count"):
        n = int(n)
    HOST_SYNC_STATS.count_pulls += 1
    cap = next_pow2(n)
    out = _compact_fn(rel.capacity, rel.arity, cap)(rel.data, mask)
    return Relation(out, n, rel.sorted_by)


@lru_cache(maxsize=None)
def _project_fn(cap, ar, cols):
    @jax.jit
    def tg_project(data):
        return project_core(data, cols)
    return tg_project


def project(rel: Relation, cols) -> Relation:
    if not cols:
        cols = (0,)
    return Relation(_project_fn(rel.capacity, rel.arity, tuple(cols))(rel.data),
                    rel.count)


# ---------------------------------------------------------------------------
# sort-merge join (single int32 key column; multi-column keys are packed by
# the planner with post-join verification)
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def _sortby_fn(cap, ar, key_col, pallas):
    @jax.jit
    def tg_keysort(data):
        return keysort_core(data, key_col, pallas=pallas)
    return tg_keysort


def sort_by(rel: Relation, key_col: int) -> Relation:
    """Sort by one key column; skipped when ``sorted_by`` already starts with
    that column (a lexsorted relation is sorted by its primary column)."""
    if (sorted_store_enabled() and rel.sorted_by
            and rel.sorted_by[0] == key_col):
        SORT_STATS.skipped += 1
        return rel
    data = _sortby_fn(rel.capacity, rel.arity, key_col,
                      use_pallas())(rel.data)
    SORT_STATS.key_sort += 1
    return Relation(data, rel.count, (key_col,))


@lru_cache(maxsize=None)
def _join_count_fn(lcap, lar, rcap, rar, lkey, rkey):
    @jax.jit
    def tg_join_count(l, r):
        return join_count_core(l, r, lkey, rkey)
    return tg_join_count


@lru_cache(maxsize=None)
def _join_mat_fn(lcap, lar, rcap, rar, out_cap):
    @jax.jit
    def tg_join_gather(l, r, per, cum, lo, total):
        return join_gather_core(l, r, per, cum, lo, total, out_cap)
    return tg_join_gather


def sm_join(l: Relation, r: Relation, lkey: int, rkey: int):
    """Sort-merge join; returns (Relation out, matches) where out columns are
    [l cols..., r cols...] and ``matches`` is the trigger count.  Input sorts
    are skipped for relations already sorted by their join key."""
    if l.count == 0 or r.count == 0:
        return Relation.empty(l.arity + r.arity, dtype=l.dtype), 0
    ls = sort_by(l, lkey)
    rs = sort_by(r, rkey)
    total, per, cum, lo = _join_count_fn(
        l.capacity, l.arity, r.capacity, r.arity, lkey, rkey)(ls.data, rs.data)
    with TraceAnnotation("tg.pull", site="count"):
        total = int(total)
    HOST_SYNC_STATS.count_pulls += 1
    if total == 0:
        return Relation.empty(l.arity + r.arity), 0
    out_cap = next_pow2(total)
    out = _join_mat_fn(l.capacity, l.arity, r.capacity, r.arity, out_cap)(
        ls.data, rs.data, per, cum, lo, total)
    return Relation(out, total), total


def cross(l: Relation, r: Relation):
    """Cartesian product (rare in practice; needed for disconnected bodies)."""
    if l.count == 0 or r.count == 0:
        return Relation.empty(l.arity + r.arity, dtype=l.dtype), 0
    total = l.count * r.count
    out_cap = next_pow2(total)
    li = jnp.repeat(jnp.arange(l.count), r.count, total_repeat_length=total)
    ri = jnp.tile(jnp.arange(r.count), l.count)[:total]
    out = jnp.full((out_cap, l.arity + r.arity), pad_of(l.data), l.data.dtype)
    rows = jnp.concatenate([l.data[li], r.data[ri]], axis=1)
    out = jax.lax.dynamic_update_slice(out, rows, (0, 0))
    return Relation(out, total), total


# ---------------------------------------------------------------------------
# antijoin (Def. 23 / redundancy filtering): drop rows whose key-tuple occurs
# in a sorted haystack relation
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def _anti_count_fn(cap, ar, hcap, har, cols):
    @jax.jit
    def tg_anti_count(data, hay_sorted):
        keep = anti_keep_core(data, hay_sorted, cols)
        return jnp.sum(keep), keep
    return tg_anti_count


def antijoin(rel: Relation, hay: Relation, cols=None) -> Relation:
    """Rows of rel whose ``cols``-tuple is NOT in hay.  The haystack lexsort
    is skipped when ``hay`` carries the full-lexsort marker (the store
    invariant); the output keeps ``rel``'s marker since compaction preserves
    row order."""
    if rel.count == 0:
        return rel
    if hay.count == 0:
        return rel
    cols = tuple(cols) if cols is not None else tuple(range(rel.arity))
    assert len(cols) == hay.arity
    hs = lexsort_rows(hay)
    n, keep = _anti_count_fn(rel.capacity, rel.arity, hs.capacity,
                             hay.arity, cols)(rel.data, hs.data)
    with TraceAnnotation("tg.pull", site="count"):
        n = int(n)
    HOST_SYNC_STATS.count_pulls += 1
    if n == rel.count:
        return rel
    cap = next_pow2(n)
    out = _compact_fn(rel.capacity, rel.arity, cap)(rel.data, keep)
    return Relation(out, n, rel.sorted_by)


# ---------------------------------------------------------------------------
# semijoin (DRed restriction): keep rows whose key-tuple occurs in a sorted
# haystack relation — the inverted Def. 23 pre-restriction used by deletion
# propagation (only facts already in the store can be over-deleted)
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def _semi_count_fn(cap, ar, hcap, har, cols):
    @jax.jit
    def tg_semi_count(data, hay_sorted):
        valid = data[:, 0] != pad_of(data)
        found = member_mask_core(project_core(data, cols), hay_sorted)
        keep = jnp.logical_and(valid, found)
        return jnp.sum(keep), keep
    return tg_semi_count


def semijoin(rel: Relation, hay: Relation, cols=None) -> Relation:
    """Rows of rel whose ``cols``-tuple IS in hay (the antijoin's
    complement).  Same sortedness contract: the haystack lexsort is skipped
    when marked, and the output keeps ``rel``'s marker."""
    if rel.count == 0 or hay.count == 0:
        return Relation.empty(rel.arity, dtype=rel.dtype)
    cols = tuple(cols) if cols is not None else tuple(range(rel.arity))
    assert len(cols) == hay.arity
    hs = lexsort_rows(hay)
    n, keep = _semi_count_fn(rel.capacity, rel.arity, hs.capacity,
                             hay.arity, cols)(rel.data, hs.data)
    with TraceAnnotation("tg.pull", site="count"):
        n = int(n)
    HOST_SYNC_STATS.count_pulls += 1
    if n == rel.count:
        return rel
    cap = next_pow2(n)
    out = _compact_fn(rel.capacity, rel.arity, cap)(rel.data, keep)
    return Relation(out, n, rel.sorted_by)


# ---------------------------------------------------------------------------
# union / append / merge
# ---------------------------------------------------------------------------
def union(a: Relation, b: Relation, dedupe: bool = True) -> Relation:
    """Concat-union.  With ``dedupe`` the result is lexsorted (dedup sorts);
    without, the concatenation clears any sortedness marker."""
    if a.count == 0:
        return b
    if b.count == 0:
        return a
    n = a.count + b.count
    cap = next_pow2(n)
    data = jnp.full((cap, a.arity), pad_of(a.data), a.data.dtype)
    data = jax.lax.dynamic_update_slice(data, a.data[:a.count], (0, 0))
    data = jax.lax.dynamic_update_slice(data, b.data[:b.count], (a.count, 0))
    out = Relation(data, n)
    return dedup(out) if dedupe else out


def fit_rows(data, out_cap):
    """Slice or PAD-extend to ``out_cap`` rows (rows >= count are PAD either
    way) so jit caches key on the planned output bucket, not the input's."""
    cap = data.shape[0]
    if cap == out_cap:
        return data
    if cap > out_cap:
        return data[:out_cap]
    return jnp.concatenate(
        [data, jnp.full((out_cap - cap, data.shape[1]), pad_of(data),
                        data.dtype)])


@lru_cache(maxsize=None)
def _merge_fn(cap, bcap, ar):
    @jax.jit
    def tg_merge(A, B, na, nb):
        return merge_core(A, B, na, nb)
    return tg_merge


def merge_union(a: Relation, b: Relation) -> Relation:
    """Incremental sorted union of two DISJOINT row sets: two lexicographic
    binary-search passes place every row, instead of concat + full resort.
    Inputs are lexsorted first (free when they carry the marker); the output
    is lexsorted and marked.  Disjointness (e.g. delta antijoined against the
    store) is required — equal rows across inputs would collide on one slot."""
    assert a.arity == b.arity
    if b.count == 0:
        return lexsort_rows(a)
    if a.count == 0:
        return lexsort_rows(b)
    if b.count > a.count:   # search the smaller side into the larger
        a, b = b, a
    a = lexsort_rows(a)
    b = lexsort_rows(b)
    n = a.count + b.count
    out_cap = next_pow2(n)
    out = _merge_fn(out_cap, b.capacity, a.arity)(
        fit_rows(a.data, out_cap), b.data, a.count, b.count)
    SORT_STATS.merges += 1
    return Relation(out, n, lex_order(a.arity))


@lru_cache(maxsize=None)
def _diff_fn(cap, hcap, ar, out_cap):
    @jax.jit
    def tg_merge_diff(A, B):
        return merge_diff_core(A, B, out_cap)
    return tg_merge_diff


def merge_diff(a: Relation, b: Relation) -> Relation:
    """Incremental sorted set-difference ``a - b`` (full rows), the deletion
    counterpart of ``merge_union``: both sides are lexsorted first (free when
    they carry the marker), every ``a`` row is one binary-search membership
    probe into ``b``, and the surviving rows compact in place — no re-sort of
    the store.  Output is lexsorted and marked."""
    assert a.arity == b.arity
    if a.count == 0 or b.count == 0:
        return lexsort_rows(a)
    a = lexsort_rows(a)
    b = lexsort_rows(b)
    # keep a's buffer capacity: the difference always fits, and preserving
    # the shape keeps downstream jit signatures stable across delete calls
    # (a shrink-to-fit here would recompile every store consumer)
    out_cap = a.capacity
    out, n = _diff_fn(a.capacity, b.capacity, a.arity, out_cap)(a.data,
                                                                b.data)
    with TraceAnnotation("tg.pull", site="count"):
        n = int(n)
    HOST_SYNC_STATS.count_pulls += 1
    SORT_STATS.merges += 1
    return Relation(out, n, lex_order(a.arity))
