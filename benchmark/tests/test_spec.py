"""BENCHMARK.json against the benchmark's contract, and discovery by
name: a configuration, a traffic mix and a per-layer metric are added
by adding files and entries alone."""

import json
import os
import re
import shutil

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == KEYS
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs_and_cells(bench):
    under = tuple(p.rstrip("/") + "/" for p in bench["paths"])
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(under) and c["file"] not in files
        files.add(c["file"])
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = set()
    assert 1 <= len(bench["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    names = [x["name"] for x in bench["configs"]]
    names2 = [x["name"] for x in bench["workloads"]]
    names3 = [x["name"] for x in bench["end_to_end"] + bench["per_layer"]]
    for group in (names, names2, names3):
        assert len(group) == len(set(group))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reporting
    for cell in cells:
        got = [m["name"] for m in bench["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in got and len(got) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_cell_loads(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.runner.run and cell.per_layer
        assert all(m.reader.read({}) is None for m in cell.per_layer)


def test_added_files_are_found(tmp_path, bench):
    """A throwaway configuration, mix and metric, added as files and
    entries only, beside the first cell."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    b = tmp_path / "benchmark"
    w0 = bench["workloads"][0]
    c0 = next(c for c in bench["configs"] if c["name"] == w0["config"])
    full = json.loads((tmp_path / c0["file"]).read_text())
    cfg = dict(full, validators=full["validators"] // 2)
    (b / "configs" / "half-registry.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / (w0["traffic"] + ".json")).read_text())
    mix["description"] = "a throwaway mix"
    (b / "traffic" / "throwaway.json").write_text(json.dumps(mix))
    (b / "metrics" / "calls_per_s.py").write_text(
        "def read(facts):\n    return facts.get('calls', 0) / 2\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "half-registry", "source": "x",
                           "file": "benchmark/configs/half-registry.json",
                           "reduced": ["validators"], "why": "x"})
    new["workloads"].append({"name": "half-throwaway",
                             "config": "half-registry",
                             "traffic": "throwaway", "chips": 1, "why": "x"})
    moves = next(m["name"] for m in bench["end_to_end"]
                 if m["name"] != "setup_s")
    new["per_layer"].append({"name": "calls_per_s", "unit": "1/s",
                             "better": "higher", "source": "host_clock",
                             "layer": "dispatch", "moves": moves,
                             "workloads": ["half-throwaway"]})
    for m in new["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("half-throwaway")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.load_cell("half-throwaway", str(tmp_path), str(b))
    assert cell.config["validators"] * 2 == full["validators"]
    assert cell.mix["description"] == "a throwaway mix"
    assert cell.runner.__name__.endswith(mix["kind"])
    readers = {m.name: m.reader for m in cell.per_layer}
    assert readers["calls_per_s"].read({"calls": 8}) == 4
    # and the cells that were there still load, unchanged
    old = spec.load_cell(w0["name"], str(tmp_path), str(b))
    assert "calls_per_s" not in {m.name for m in old.per_layer}


def test_missing_file_is_an_error(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "nope.json"}],
        "workloads": [{"name": "w", "config": "c", "traffic": "t",
                       "chips": 1}]}))
    with pytest.raises(spec.SpecError):
        spec.load_cell("w", str(tmp_path))
    with pytest.raises(spec.SpecError):
        spec.load_cell("absent", str(tmp_path))
