"""Plain Datalog reference: semi-naive evaluation over Python sets.

Independent of the engine under test: its own parser for the rule files in
``bench/rules/`` and its own term table.  Terms are interned to whole
numbers (their rank among all base terms), facts are tuples of those
numbers, and each round joins the atoms of a rule left to right through
hash indexes on the positions already bound.

:func:`evaluate` returns ``{"terms": sorted unique base terms, "facts":
{pred: (n, arity) int64 rows of term ranks}, "rounds": productive rounds}``,
the form ``bench/compare.py`` reads.
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

_ATOM = re.compile(r"\s*([A-Za-z_][A-Za-z0-9_]*)\s*\(([^)]*)\)\s*")


def _atom(text: str):
    m = _ATOM.fullmatch(text)
    if not m:
        raise ValueError(f"bad atom: {text!r}")
    args = tuple(a.strip() for a in m.group(2).split(","))
    for a in args:
        if not a[:1].isupper():
            raise ValueError(f"constant {a!r} in {text!r}: the reference "
                             "takes variables only")
    return m.group(1), args


def parse(text: str):
    """``[(head, body)]`` with atoms as ``(pred, (var, ...))``."""
    rules = []
    for line in text.splitlines():
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        lhs, rhs = line.split("->")
        body = [_atom(a) for a in lhs.split("&")]
        head = _atom(rhs)
        bound = {v for _, args in body for v in args}
        if not set(head[1]) <= bound:
            raise ValueError(f"head variable not in the body: {line!r}")
        rules.append((head, body))
    return rules


def intern(tables: dict):
    """(sorted unique terms, {pred: int64 rows of term ranks})."""
    terms = np.unique(np.concatenate([np.asarray(t).reshape(-1)
                                      for t in tables.values()]))
    ids = {p: np.searchsorted(terms, np.asarray(t)).astype(np.int64)
           for p, t in tables.items()}
    return terms, ids


class _Indexes:
    """Hash indexes of the current facts, keyed by bound positions; built on
    first use and dropped whenever the facts grow."""

    def __init__(self, facts):
        self.facts = facts
        self.cache = {}

    def get(self, pred, positions):
        key = (pred, positions)
        idx = self.cache.get(key)
        if idx is None:
            idx = defaultdict(list)
            for f in self.facts.get(pred, ()):
                idx[tuple(f[i] for i in positions)].append(f)
            self.cache[key] = idx
        return idx


def _fire(head, body, first_rows, indexes):
    """Head tuples of ``body`` with its first atom ranging over
    ``first_rows`` and the rest over the indexed facts."""
    pred0, args0 = body[0]
    out = set()
    steps = []
    bound = []
    for v in args0:
        if v not in bound:
            bound.append(v)
    for pred, args in body[1:]:
        positions = tuple(i for i, v in enumerate(args) if v in bound)
        keyvars = tuple(args[i] for i in positions)
        new = [(i, v) for i, v in enumerate(args) if v not in bound
               and v not in args[:i]]
        same = [(i, args.index(v)) for i, v in enumerate(args)
                if v not in bound and args.index(v) != i]
        steps.append((indexes.get(pred, positions), keyvars, new, same))
        bound += [v for _, v in new]
    for row in first_rows:
        env = {}
        ok = True
        for v, x in zip(args0, row):
            if env.setdefault(v, x) != x:
                ok = False
                break
        if not ok:
            continue
        envs = [env]
        for idx, keyvars, new, same in steps:
            nxt = []
            for e in envs:
                for f in idx.get(tuple(e[v] for v in keyvars), ()):
                    if all(f[i] == f[j] for i, j in same):
                        e2 = dict(e)
                        for i, v in new:
                            e2[v] = f[i]
                        nxt.append(e2)
            envs = nxt
            if not envs:
                break
        for e in envs:
            out.add(tuple(e[v] for v in head[1]))
    return out


def _rotate(body, j):
    """The body with atom ``j`` first (the delta atom drives the join)."""
    return [body[j]] + body[:j] + body[j + 1:]


def evaluate(rules_text: str, tables: dict, max_rounds: int | None = None):
    """Least model of the rules over ``tables`` (``{pred: (n, arity)
    array of terms}``), or the facts after ``max_rounds`` productive
    rounds."""
    rules = parse(rules_text)
    terms, ids = intern(tables)
    facts = defaultdict(set)
    for p, rows in ids.items():
        facts[p].update(map(tuple, rows.tolist()))
    rounds = 0
    delta = None
    while max_rounds is None or rounds < max_rounds:
        indexes = _Indexes(facts)
        derived = defaultdict(set)
        for head, body in rules:
            if delta is None:       # first round: every rule over the base
                derived[head[0]] |= _fire(head, body, facts[body[0][0]],
                                          indexes)
                continue
            for j, (pred, _) in enumerate(body):
                if delta.get(pred):
                    derived[head[0]] |= _fire(head, _rotate(body, j),
                                              delta[pred], indexes)
        delta = {p: rows - facts[p] for p, rows in derived.items()}
        delta = {p: rows for p, rows in delta.items() if rows}
        if not delta:
            break
        rounds += 1
        for p, rows in delta.items():
            facts[p] |= rows
    arity = {p: len(a) for (p, a), _ in rules}
    arity.update({p: len(a) for _, body in rules for p, a in body})
    arity.update({p: rows.shape[1] for p, rows in ids.items()})
    out = {p: np.array(sorted(rows), np.int64).reshape(-1, arity[p])
           for p, rows in facts.items()}
    return {"terms": terms, "facts": out, "rounds": rounds}
