"""`BENCHMARK.json` against the contract's own rules, and every file it
names by name is there."""
import json
import re

import pytest
from bench_testlib import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head).*(size|dim)"
                   r"|_dim$|_rank$|expansion|experts_per_tok")


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size(man):
    assert set(man.data) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(man.data["paths"]) <= 16
    for p in man.data["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (REPO / p).is_dir()
    cmd = man.data["command"]
    assert 1 <= len(cmd) <= 32 and all(line_ok(w) for w in cmd)
    assert cmd[1].startswith(man.data["paths"][0] + "/")


def test_run_seconds_fits_a_full_check_with_24_cells(man):
    rs = man.data["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(man):
    names = [c["name"] for c in man.data["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in man.data["workloads"]}
    files = set()
    for c in man.data["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in man.data["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        body = json.loads((REPO / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        for key in ("entry", "reference", "item", "model", "train"):
            assert key in body, (c["name"], key)
        man.find("entries", body["entry"] + ".py")
        man.find("references", body["reference"] + ".py")


def test_workloads(man):
    cells = man.data["workloads"]
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names) and 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    configs = {c["name"] for c in man.data["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"])
        mix = man.json_of("traffic", w["traffic"])
        assert mix["chips"] == w["chips"]
        limits = man.json_of("limits", w["name"])["numbers"]
        assert all("limit" in v for v in limits.values())


def cells_reporting(man, metric):
    return metric.get("workloads") or [w["name"] for w in man.data["workloads"]]


def test_end_to_end_metrics(man):
    e2e = man.data["end_to_end"]
    assert 1 <= len(e2e) <= 16 and "setup_s" in [m["name"] for m in e2e]
    cells = {w["name"] for w in man.data["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(cells_reporting(man, m)) <= cells
        how = man.json_of("e2e_metrics", m["name"])
        man.find("readers", how["reader"] + ".py")
    for cell in cells:
        mine = [m["name"] for m in man.metrics_of(cell, "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2


def test_per_layer_metrics(man):
    per_layer = man.data["per_layer"]
    assert 1 <= len(per_layer) <= 128
    names = [m["name"] for m in per_layer + man.data["end_to_end"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in man.data["end_to_end"]}
    for m in per_layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        # what refused PR 22: a layer is one token, like every other name
        assert NAME.match(m["layer"]), m["layer"]
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e
        moved = set(cells_reporting(man, e2e[m["moves"]]))
        assert set(cells_reporting(man, m)) <= moved
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        how = man.json_of("layer_metrics", m["name"])
        assert how["layer"] == m["layer"] and how["moves"] == m["moves"]
        man.find("readers", how["reader"] + ".py")
    for w in man.data["workloads"]:
        assert man.metrics_of(w["name"], "per_layer")
    rooflines = [m for m in per_layer if m["name"].endswith("roofline")]
    for r in rooflines:   # a kernel's roofline stands beside the step's mfu
        assert any("mfu" in m["name"].split(".") and m["moves"] == r["moves"]
                   and set(cells_reporting(man, r))
                   <= set(cells_reporting(man, m)) for m in per_layer)


def test_files_under_paths_are_named_from_allowed_characters(man):
    for p in man.data["paths"]:
        for f in (REPO / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert PATH.match(str(f.relative_to(REPO))), f
