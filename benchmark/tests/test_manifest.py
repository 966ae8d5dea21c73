"""BENCHMARK.json against its contract's limits and against the files
under benchmark/: every entry's file exists and every file has an
entry; the peaks table and the work function refuse what they do not
know."""

import glob
import importlib
import json
import os
import re

import pytest

from benchmark.harness import work
from benchmark.harness.manifest import BENCH_DIR, ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return Manifest()


def test_keys_and_limits(m):
    d = m.doc
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert d["paths"] == ["benchmark"]
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    n_cells = len(d["workloads"])
    # 2 + 14 x cells runs of run_seconds + 60 s, 180 s a cell to compile,
    # 1200 s spare, inside 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= n_cells <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert "setup_s" in {e["name"] for e in d["end_to_end"]}
    for e in d["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    for e in d["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert e["source"] in SOURCES


def test_names_and_units(m):
    d = m.doc
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in d[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" and k != "source" \
                        or (k == "source" and group == "configs"):
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], (e["name"], k)
    assert len(names) == len(set(names))
    for w in d["workloads"]:
        assert NAME.match(w["traffic"]) and w["config"] in m.configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in d["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["source"]) <= 200


def test_every_cell_reports_enough(m):
    e2e = {e["name"] for e in m.doc["end_to_end"]}
    for cell in m.cells:
        mine = {e["name"] for e in m.end_to_end(cell)}
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert m.per_layer(cell), cell
    for e in m.doc["per_layer"]:
        assert e["moves"] in e2e
        moved = next(x for x in m.doc["end_to_end"]
                     if x["name"] == e["moves"])
        for cell in e["workloads"]:
            assert cell in m.cells
            assert cell in moved.get("workloads", list(m.cells)), \
                (e["name"], cell)


def test_every_entry_has_its_file_and_every_file_its_entry(m):
    def stems(sub):
        return {os.path.splitext(os.path.basename(p))[0]
                for p in glob.glob(os.path.join(BENCH_DIR, sub, "*.json"))}
    assert stems("workloads") == set(m.cells)
    assert stems("metrics") == {e["name"] for e in m.doc["per_layer"]}
    files = {c["file"] for c in m.doc["configs"]}
    assert len(files) == len(m.doc["configs"])
    assert {os.path.join("benchmark", "configs", s + ".json")
            for s in stems("configs")} == files
    for c in m.doc["configs"]:
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["source"] == c["source"]
        assert set(body["reduced"]) == set(c["reduced"])
        assert body["guarantees"] and "assumed" in body
        importlib.import_module("benchmark.deployments." + body["driver"])
    for cell in m.cells:
        wl = m.workload_params(cell)
        importlib.import_module(
            "benchmark.traffic." + wl["traffic"]["generator"])
        assert wl["warm_buckets"] and wl["trace_slice"]["length_s"] > 0
    used = set()
    for e in m.doc["per_layer"]:
        spec = m.metric_params(e["name"])
        for k in ("layer", "unit", "moves", "workloads"):
            assert spec[k] == e[k], (e["name"], k)
        importlib.import_module("benchmark.readers." + spec["reader"])
        used.add(spec["reader"])
    readers = {os.path.splitext(os.path.basename(p))[0] for p in
               glob.glob(os.path.join(BENCH_DIR, "readers", "*.py"))}
    assert readers - {"__init__"} == used


def test_nothing_imports_the_old_benchmarks():
    pat = re.compile(r"^\s*(import|from)\s+(bench|chip_smoke|tools)\b",
                     re.M)
    for path in glob.glob(os.path.join(BENCH_DIR, "**", "*.py"),
                          recursive=True):
        assert not pat.search(open(path).read()), path


def test_unknown_device_kind_is_refused():
    assert work.peaks("TPU v5 lite")["int_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.ed25519_roofline(1000, 0.01, "cpu")
    with pytest.raises(ValueError):
        work.ed25519_roofline(0, 0.01, "TPU v5 lite")


def test_roofline_arithmetic():
    assert work.OPS_PER_SIG == 3724 * 800
    r = work.ed25519_roofline(8192, 0.039, "TPU v5 lite")
    assert r["bound"] == "compute"
    assert abs(r["pct"] - 100 * (8192 * 2979200 / 393e12) / 0.039) < 1e-12
    assert 0 < r["pct"] < 100
