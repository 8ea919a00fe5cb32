"""BENCHMARK.json against the benchmark's contract: names, units,
metric sources, bounds, every configuration used, every `moves` an
end-to-end metric of each cell that reports it, at most half the cells
on four chips, and every file it names present under its paths."""
import json
import math
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51


def test_names_and_keys(manifest):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source",
                           "workloads"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    for group, allowed in keys.items():
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
        for e in manifest[group]:
            assert set(e) <= allowed, (group, set(e) - allowed)
            assert NAME.match(e["name"]), e["name"]
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in manifest[g]]
    assert len(metric_names) == len(set(metric_names))


def test_configs(manifest):
    paths = manifest["paths"]
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    assert 1 <= len(manifest["configs"]) <= 24
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert c["name"] in used, f"{c['name']} has no cell"
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in paths)
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and k in conf
            assert not (k.endswith("_dim") or k.endswith("_rank")
                        or k in ("hidden_size", "intermediate_size",
                                 "num_experts_per_tok"))


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in cells if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in cells)
    assert four <= max(1, math.floor(len(cells) * 0.5))
    for w in cells:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert _line(w["why"])
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "traffic", f"{w['traffic']}.json"))


def test_metrics(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert m["source"] in E2E_SOURCES
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "metrics", f"{m['name']}.py"))
        for w in m.get("workloads", []):
            assert w in cells
    layers = {}
    for m in manifest["per_layer"]:
        assert _line(m["layer"])
        target = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in target.get("workloads", cells), (m["name"], w)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for w in cells:
        mine = [m for m in manifest["end_to_end"]
                if w in m.get("workloads", cells)]
        assert "setup_s" in {m["name"] for m in mine}
        assert len(mine) >= 2
        assert any(w in m.get("workloads", cells)
                   for m in manifest["per_layer"])
