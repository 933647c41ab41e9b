"""LUBM base facts after the UBA generator's profile, as host arrays.

The profile is that of Guo, Pan & Heflin, "LUBM: A benchmark for OWL
knowledge base systems", J. Web Semantics 3(2-3), 2005 (the Univ-Bench
Artificial data generator, UBA).  Each range in the configuration's
``params`` is one of the profile's; the mapping onto the 12 base
predicates of the LUBM-L rules is:

==========================  =========================================
UBA triple                  base fact
==========================  =========================================
Dept subOrganizationOf U    ``subOrg(dept, univ)``
Group subOrganizationOf D   ``subOrg(group, dept)``
X a FullProfessor, worksFor ``fullProf(x, dept)`` (and the other three
                            faculty kinds: ``assocProf``,
                            ``assistProf``, ``lecturer``)
P headOf D                  ``headOf(p, dept)``
S a UndergraduateStudent,   ``ugStudent(s, dept)``
memberOf D
S a GraduateStudent, ...    ``gradStudent(s, dept)``
F teacherOf C               ``teaches(f, course)``
S takesCourse C             ``takes(s, course)``
S advisor P                 ``advisor(s, p)``
B publicationAuthor F       ``publication(b, f)``
==========================  =========================================

Terms are URIs in UBA's own form (``http://www.Department3.University0.edu/
FullProfessor2``), so the dictionary interns real strings.

Every count is drawn as a *balanced* permutation: the values of a range
are dealt out in turn and the seed only shuffles who gets which.  So every
seed yields the same number of entities and facts of each kind, and the
seed changes the structure (who teaches, takes and advises what), not the
amount of work.
"""
from __future__ import annotations

import numpy as np


def balanced(rng, lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` whole numbers spread evenly over ``[lo, hi]`` (one alone is the
    middle of the range), in an order drawn from ``rng``.  The multiset
    depends on ``n`` alone."""
    vals = lo + ((np.arange(n) + 0.5) * (hi - lo + 1) / n).astype(np.int64)
    return rng.permutation(vals)


def _pick(rng, k: int, pool: list) -> list:
    """``k`` distinct members of ``pool``, drawn from ``rng``."""
    return [pool[i] for i in rng.choice(len(pool), size=k, replace=False)]


FACULTY_KINDS = (("fullProf", "FullProfessor", "full_professors",
                  "full"),
                 ("assocProf", "AssociateProfessor",
                  "associate_professors", "associate"),
                 ("assistProf", "AssistantProfessor",
                  "assistant_professors", "assistant"),
                 ("lecturer", "Lecturer", "lecturers", "lecturer"))
PROFESSOR_KINDS = ("fullProf", "assocProf", "assistProf")


def generate(config: dict, seed: int) -> dict:
    """``{pred: (n, 2) str ndarray}`` of base facts for the configuration's
    ``universities`` and ``profile`` from ``seed`` (any non-negative whole
    number)."""
    params = config["profile"]
    rng = np.random.default_rng(seed)
    rows = {p: [] for p in ("subOrg", "fullProf", "assocProf", "assistProf",
                            "lecturer", "headOf", "gradStudent",
                            "ugStudent", "teaches", "takes", "advisor",
                            "publication")}
    n_univ = config["universities"]
    depts = balanced(rng, *params["departments"], n_univ)
    n_dept = int(depts.sum())
    # per-department counts, balanced over all departments
    kind_counts = {pred: balanced(rng, *params[key], n_dept)
                   for pred, _, key, _ in FACULTY_KINDS}
    groups = balanced(rng, *params["research_groups"], n_dept)
    n_fac = int(sum(c.sum() for c in kind_counts.values()))
    # per-faculty counts, balanced over all faculty (in generation order)
    ug_per = balanced(rng, *params["undergraduates_per_faculty"], n_fac)
    grad_per = balanced(rng, *params["graduates_per_faculty"], n_fac)
    courses_per = balanced(rng, *params["courses_per_faculty"], n_fac)
    gcourses_per = balanced(rng, *params["graduate_courses_per_faculty"],
                            n_fac)
    pubs = {short: balanced(rng, *params["publications"][short],
                            int(kind_counts[pred].sum()))
            for pred, _, _, short in FACULTY_KINDS}
    n_ug = int(ug_per.sum())
    n_grad = int(grad_per.sum())
    ug_takes = balanced(rng, *params["undergraduate_courses_taken"], n_ug)
    grad_takes = balanced(rng, *params["graduate_courses_taken"], n_grad)
    ug_advised = rng.permutation(
        np.arange(n_ug) < int(n_ug * params["undergraduate_advised_share"]))

    d_all = f_all = ug_all = grad_all = 0
    pub_i = {short: 0 for *_, short in FACULTY_KINDS}
    for u in range(n_univ):
        univ = f"http://www.University{u}.edu"
        for d in range(int(depts[u])):
            dept = f"http://www.Department{d}.University{u}.edu"
            rows["subOrg"].append((dept, univ))
            for g in range(int(groups[d_all])):
                rows["subOrg"].append((f"{dept}/ResearchGroup{g}", dept))
            faculty, professors = [], []
            for pred, cls, _, short in FACULTY_KINDS:
                for i in range(int(kind_counts[pred][d_all])):
                    f = f"{dept}/{cls}{i}"
                    rows[pred].append((f, dept))
                    faculty.append(f)
                    if pred in PROFESSOR_KINDS:
                        professors.append(f)
                    for b in range(int(pubs[short][pub_i[short]])):
                        rows["publication"].append((f"{f}/Publication{b}",
                                                    f))
                    pub_i[short] += 1
            n_full = int(kind_counts["fullProf"][d_all])
            rows["headOf"].append(
                (f"{dept}/FullProfessor{int(rng.integers(n_full))}", dept))
            courses, gcourses = [], []
            for f in faculty:
                for _ in range(int(courses_per[f_all])):
                    c = f"{dept}/Course{len(courses)}"
                    courses.append(c)
                    rows["teaches"].append((f, c))
                for _ in range(int(gcourses_per[f_all])):
                    c = f"{dept}/GraduateCourse{len(gcourses)}"
                    gcourses.append(c)
                    rows["teaches"].append((f, c))
                f_all += 1
            fac0 = f_all - len(faculty)
            n_ug_d = int(ug_per[fac0:f_all].sum())
            n_grad_d = int(grad_per[fac0:f_all].sum())
            for s in range(n_ug_d):
                st = f"{dept}/UndergraduateStudent{s}"
                rows["ugStudent"].append((st, dept))
                for c in _pick(rng, int(ug_takes[ug_all]), courses):
                    rows["takes"].append((st, c))
                if ug_advised[ug_all]:
                    rows["advisor"].append(
                        (st, professors[int(rng.integers(len(professors)))]))
                ug_all += 1
            for s in range(n_grad_d):
                st = f"{dept}/GraduateStudent{s}"
                rows["gradStudent"].append((st, dept))
                for c in _pick(rng, int(grad_takes[grad_all]), gcourses):
                    rows["takes"].append((st, c))
                rows["advisor"].append(
                    (st, professors[int(rng.integers(len(professors)))]))
                grad_all += 1
            d_all += 1
    return {p: np.array(r, dtype=str).reshape(-1, 2) for p, r in rows.items()}
