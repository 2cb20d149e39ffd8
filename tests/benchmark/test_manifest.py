"""``BENCHMARK.json`` keeps to its contract, every file it names is there
under its own name, and the harness names no cell, configuration or
metric: a later PR adds each as files of its own."""

import json
import os
import re

import pytest

from harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "expansion", "experts_per_tok")


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    path = os.path.join(manifest.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["command"]) <= 32
    assert all(_line(word) for word in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(manifest.ROOT, p))
    # The command names no file of the repo outside ``paths``.
    for word in m["command"][1:]:
        if os.path.exists(os.path.join(manifest.ROOT, word)):
            assert any(word.startswith(p + "/") for p in m["paths"])


def test_full_check_fits_its_time(m):
    # 2 + 14 x cells runs of run_seconds + 60, 180 s a cell to compile,
    # 1200 s spare, at the full 24 cells, inside 43200 s.
    cells = 24
    assert ((2 + 14 * cells) * (m["run_seconds"] + 60) + cells * 180
            + 1200) <= 43200


def test_configs(m):
    assert 1 <= len(m["configs"]) <= 24
    names = [c["name"] for c in m["configs"]]
    files = [c["file"] for c in m["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS)
        cfg = manifest.load_json(manifest.ROOT, c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for kind in ("builder", "reference"):
            assert NAME.match(cfg[kind])
            kind_dir = "builders" if kind == "builder" else "reference"
            assert os.path.isfile(os.path.join(
                manifest.BENCH_DIR, kind_dir, cfg[kind] + ".py"))


def test_workloads(m):
    assert 1 <= len(m["workloads"]) <= 24
    names = [w["name"] for w in m["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in m["configs"]}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        traffic = os.path.join(manifest.BENCH_DIR, "traffic",
                               w["traffic"] + ".json")
        assert os.path.isfile(traffic)
        with open(traffic) as f:
            assert json.load(f)["name"] == w["traffic"]
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_metrics(m):
    assert 1 <= len(m["end_to_end"]) <= 16
    assert 1 <= len(m["per_layer"]) <= 128
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names)) == len(names)
    cells = [w["name"] for w in m["workloads"]]
    reports = {}    # end-to-end metric -> the cells that report it
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
        reports[e["name"]] = e.get("workloads", cells)
        assert set(reports[e["name"]]) <= set(cells)
    assert reports["setup_s"] == cells
    for cell in cells:
        assert sum(cell in reports[name] for name in sorted(reports)) >= 2
    layered = set()
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert p["better"] in ("lower", "higher")
        assert p["source"] in SOURCES and _line(p["layer"])
        assert p["moves"] in reports
        for cell in p.get("workloads", cells):
            assert cell in reports[p["moves"]]
            layered.add(cell)
        if p["name"].endswith("_roofline") or "mfu" in p["name"]:
            assert p["unit"] == "%"
        assert os.path.isfile(os.path.join(
            manifest.BENCH_DIR, "layer_metrics", p["name"] + ".py"))
    assert layered == set(cells)


def test_files_under_paths_are_named_from_a_names_characters(m):
    for p in m["paths"]:
        for folder, dirs, files in os.walk(os.path.join(manifest.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name),
                                      manifest.ROOT)
                assert PATH.match(rel), rel


def test_harness_names_no_cell_configuration_or_metric(m):
    """What belongs to one cell, configuration or metric sits in a file of
    its own, so the general code never has to be edited to add one."""
    names = {x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in m[key]}
    names |= {w["traffic"] for w in m["workloads"]}
    general = [os.path.join(manifest.BENCH_DIR, "run.py")]
    harness = os.path.join(manifest.BENCH_DIR, "harness")
    general += [os.path.join(harness, f) for f in os.listdir(harness)
                if f.endswith(".py")]
    for path in general:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(
                r"(?<![A-Za-z0-9_.\-])" + re.escape(name)
                + r"(?![A-Za-z0-9_\-])", text), (name, path)
