"""BENCHMARK.json keeps to the rules for its keys, names and units, and
every name in it finds its files."""
import json
import os
import re

import pytest

from bench import harness

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    for n in names:
        assert NAME.fullmatch(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_name_finds_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert c["file"].startswith(spec["paths"][0] + "/")
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4)
        loaded = harness.load_cell(w["name"])
        assert loaded["config"]["name"] == w["config"]
        assert sorted(configs[w["config"]]["reduced"]) == \
            sorted(loaded["config"]["reduced"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
