"""The plain references against independent evaluations."""
import numpy as np
import pytest

from bench import harness
from bench.reference import datalog, tc_matrix

TINY_UBA = {"universities": 1, "profile": {
    "departments": [1, 1], "full_professors": [2, 3],
    "associate_professors": [2, 3], "assistant_professors": [1, 2],
    "lecturers": [1, 2], "undergraduates_per_faculty": [2, 3],
    "graduates_per_faculty": [1, 2], "courses_per_faculty": [1, 2],
    "graduate_courses_per_faculty": [1, 2],
    "undergraduate_courses_taken": [2, 4], "graduate_courses_taken": [1, 3],
    "undergraduate_advised_share": 0.2,
    "publications": {"full": [2, 3], "associate": [1, 2],
                     "assistant": [1, 2], "lecturer": [0, 1]},
    "research_groups": [2, 3]}}


def as_atoms(ref):
    """{(pred, terms)} of a reference result."""
    terms = ref["terms"].tolist()
    return {(p, tuple(terms[i] for i in row))
            for p, rows in ref["facts"].items() for row in rows.tolist()}


def rules_text(name):
    with open(f"{harness.BENCH}/rules/{name}.dl") as f:
        return f.read()


@pytest.mark.parametrize("seed", [0, 5, 2**33 + 1])
def test_lubm_reference_equals_the_chase(seed):
    from repro.core.chase import chase
    from repro.core.terms import Atom, parse_program
    from bench.generators import uba
    text = rules_text("lubm_l")
    tables = uba.generate(TINY_UBA, seed)
    base = [Atom(p, tuple(r)) for p, a in tables.items() for r in a.tolist()]
    program = parse_program(text)
    preds = {a.pred for r in program.rules for a in (r.head, *r.body)}
    want = {(f.pred, f.args) for f in chase(program, base).facts | set(base)
            if f.pred in preds}
    assert as_atoms(datalog.evaluate(text, tables)) == want


def brute_closure(edges):
    succ = {}
    for a, b in edges:
        succ.setdefault(a, set()).add(b)
    out = set()
    for a in succ:
        seen, todo = set(), list(succ[a])
        while todo:
            x = todo.pop()
            if x not in seen:
                seen.add(x)
                todo += succ.get(x, ())
        out |= {(a, b) for b in seen}
    return out


@pytest.mark.parametrize("n,d,seed", [(12, 1, 0), (30, 2, 1), (60, 50, 2)])
def test_tc_reference_equals_brute_force(n, d, seed):
    from bench.generators import uniform_digraph
    tables = uniform_digraph.generate(
        {"nodes": n, "out_degree": d}, seed)
    edges = {tuple(e) for e in tables["e"].tolist()}
    got = as_atoms(tc_matrix.evaluate(rules_text("tc"), tables))
    assert {f for p, f in got if p == "T"} == brute_closure(edges)
    assert {f for p, f in got if p == "e"} == edges
    # the generic Datalog reference agrees on the same rules
    assert as_atoms(datalog.evaluate(rules_text("tc"), tables)) == got


def test_stopping_short_loses_facts():
    """``max_rounds`` short of the fixpoint leaves facts out, in both."""
    from bench.generators import uba, uniform_digraph
    t = uniform_digraph.generate(
        {"nodes": 30, "out_degree": 1}, 4)
    full = tc_matrix.evaluate(rules_text("tc"), t)
    short = tc_matrix.evaluate(rules_text("tc"), t, full["rounds"] - 1)
    assert as_atoms(short) < as_atoms(full)
    t = uba.generate(TINY_UBA, 1)
    full = datalog.evaluate(rules_text("lubm_l"), t)
    short = datalog.evaluate(rules_text("lubm_l"), t, full["rounds"] - 1)
    assert as_atoms(short) < as_atoms(full)


def test_tc_reference_refuses_other_rules():
    with pytest.raises(ValueError):
        tc_matrix.evaluate("e(X, Y) -> T(Y, X)\n",
                           {"e": np.array([[1, 2]], np.int64)})
