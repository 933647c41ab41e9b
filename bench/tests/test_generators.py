"""The seeded generators: the profile's ranges, fixed amounts of work."""
import json

import numpy as np
import pytest

from bench import harness
from bench.generators import uba, uniform_digraph


def config(name):
    with open(f"{harness.BENCH}/configs/{name}.json") as f:
        return json.load(f)


def small_uba():
    c = config("lubm-uba-l")
    c["universities"] = 2
    return c


def between(values, lo, hi):
    return min(values) >= lo and max(values) <= hi


def test_uba_counts_fall_inside_the_profile():
    c = small_uba()
    p = c["profile"]
    t = uba.generate(c, 2**40 + 3)
    dept = {}
    for pred in ("fullProf", "assocProf", "assistProf", "lecturer",
                 "ugStudent", "gradStudent"):
        for x, d in t[pred].tolist():
            dept.setdefault(d, {}).setdefault(pred, []).append(x)
    assert len(dept) == sum(uba.balanced(np.random.default_rng(0),
                                         *p["departments"],
                                         c["universities"]))
    for d, kinds in dept.items():
        assert between([len(kinds["fullProf"])], *p["full_professors"])
        assert between([len(kinds["assocProf"])], *p["associate_professors"])
        assert between([len(kinds["assistProf"])],
                       *p["assistant_professors"])
        assert between([len(kinds["lecturer"])], *p["lecturers"])
        n_fac = sum(len(kinds[k]) for k in ("fullProf", "assocProf",
                                             "assistProf", "lecturer"))
        lo, hi = p["undergraduates_per_faculty"]
        assert lo * n_fac <= len(kinds["ugStudent"]) <= hi * n_fac
        lo, hi = p["graduates_per_faculty"]
        assert lo * n_fac <= len(kinds["gradStudent"]) <= hi * n_fac
    groups = [d for g, d in t["subOrg"].tolist() if "ResearchGroup" in g]
    assert between(list(np.unique(groups, return_counts=True)[1]),
                   *p["research_groups"])
    assert sorted(d for _, d in t["headOf"].tolist()) == sorted(dept)
    heads = {h for h, _ in t["headOf"].tolist()}
    assert heads <= {x for x, _ in t["fullProf"].tolist()}
    taken = {}
    for s, _ in t["takes"].tolist():
        taken[s] = taken.get(s, 0) + 1
    ug = [taken[s] for s, _ in t["ugStudent"].tolist()]
    grad = [taken[s] for s, _ in t["gradStudent"].tolist()]
    assert between(ug, *p["undergraduate_courses_taken"])
    assert between(grad, *p["graduate_courses_taken"])
    authored = {}
    for _, f in t["publication"].tolist():
        authored[f] = authored.get(f, 0) + 1
    for pred, _, _, short in uba.FACULTY_KINDS:
        counts = [authored.get(f, 0) for f, _ in t[pred].tolist()]
        assert between(counts, *p["publications"][short])
    advised = {s for s, _ in t["advisor"].tolist()}
    assert {s for s, _ in t["gradStudent"].tolist()} <= advised
    n_ug = len(t["ugStudent"])
    assert len(advised) - len(t["gradStudent"]) == \
        int(n_ug * p["undergraduate_advised_share"])
    professors = {x for k in uba.PROFESSOR_KINDS for x, _ in t[k].tolist()}
    assert {a for _, a in t["advisor"].tolist()} <= professors
    for s, c_ in t["takes"].tolist():
        assert s.rsplit("/", 1)[0] == c_.rsplit("/", 1)[0]  # own department
        assert ("Graduate" in c_) == ("/GraduateStudent" in s)


def test_uba_seed_changes_structure_not_amount():
    c = small_uba()
    a, b, a2 = (uba.generate(c, s) for s in (1, 2**35, 1))
    assert all(np.array_equal(a[p], a2[p]) for p in a)
    assert {p: len(v) for p, v in a.items()} == \
        {p: len(v) for p, v in b.items()}
    assert not np.array_equal(a["takes"], b["takes"])


DIGRAPH = {"nodes": 80, "out_degree": 50}


@pytest.mark.parametrize("seed", [0, 2**33 + 9])
def test_digraph_has_fixed_out_degree(seed):
    t = uniform_digraph.generate(DIGRAPH, seed)["e"]
    assert t.shape == (80 * 50, 2)
    assert len(np.unique(t, axis=0)) == len(t)
    assert (np.bincount(t[:, 0], minlength=80) == 50).all()
    assert t.min() >= 0 and t.max() < 80


def test_digraph_seed_draws_the_graph():
    """Two seeds give two graphs with the same number of edges, and one
    seed gives the same graph again."""
    a = uniform_digraph.generate(DIGRAPH, 1)["e"]
    b = uniform_digraph.generate(DIGRAPH, 2**35)["e"]
    assert a.shape == b.shape
    assert {tuple(e) for e in a.tolist()} != {tuple(e) for e in b.tolist()}
    assert np.array_equal(a, uniform_digraph.generate(DIGRAPH, 1)["e"])
