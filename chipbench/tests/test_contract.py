"""BENCHMARK.json against the limits of the builder's contract that can be
checked here, and against the files it names."""

import json
import os
import re

from chipbench.tests import helpers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_keys_names_and_lengths():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"] and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(helpers.REPO, "BENCHMARK.json")) < 65536
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("chipbench/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(helpers.REPO, c["file"])) as fh:
            assert json.load(fh)["name"] == c["name"]
    configs = {c["name"] for c in b["configs"]}
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        cells.add(w["name"])
        for sub in ("traffic/" + w["traffic"], "limits/" + w["name"]):
            assert os.path.exists(os.path.join(helpers.CHIPBENCH,
                                               sub + ".json")), sub
    assert {w["config"] for w in b["workloads"]} == configs
    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert e2e["setup_s"] == cells
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= e2e[m["moves"]]
        with open(os.path.join(helpers.CHIPBENCH, "layer_metrics",
                               m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert spec["moves"] == m["moves"] and spec["unit"] == m["unit"]
        assert spec["layer"] == m["layer"]
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for cell in cells:      # every cell: setup_s, another e2e, a per-layer one
        assert sum(cell in who for who in e2e.values()) >= 2


def test_reduced_keys_are_in_the_file_and_the_corpus_is_as_long_as_it_says():
    b = _bench()
    files = {}
    for c in b["configs"]:
        with open(os.path.join(helpers.REPO, c["file"])) as fh:
            files[c["name"]] = json.load(fh)
        assert set(c["reduced"]) == set(files[c["name"]]["reduced"])
        assert all(k in files[c["name"]] for k in c["reduced"])
    for w in b["workloads"]:
        with open(os.path.join(helpers.CHIPBENCH, "traffic",
                               w["traffic"] + ".json")) as fh:
            params = json.load(fh)["params"]
        days = files[w["config"]].get("corpus_days")
        if days is not None and "buckets" in params:
            assert params["buckets"] == days * params.get("day", 1440)


def test_every_file_under_paths_is_named_plainly():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(helpers.CHIPBENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), helpers.REPO)
            assert ok.match(rel) and len(rel) <= 200, rel
