"""BENCHMARK.json against the contract, and the harness finding every cell's
files by name."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group != "end_to_end" or key == "why":
                    if key in e:
                        assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                            and "\t" not in e[key], (e["name"], key)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16


def test_setup_s_everywhere_and_pairs_once():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_four_chip_cells_are_a_quarter_at_most():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_metrics_cells_all_report_what_it_moves(metric):
    m = {x["name"]: x for x in BENCH["per_layer"]}[metric]
    moved = {x["name"]: x for x in BENCH["end_to_end"]}[m["moves"]]
    cells = m.get("workloads", CELLS)
    assert cells, metric
    for c in cells:
        assert c in CELLS
        assert "workloads" not in moved or c in moved["workloads"], (metric, c)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       metric + ".py"))


@pytest.mark.parametrize("name", CELLS)
def test_a_cells_files_are_found_by_name(name):
    cell = harness.Cell(BENCH, name, ROOT)
    assert cell.config["kind"] and cell.traffic
    assert os.path.isfile(cell._file("runners", cell.config["kind"]))
    assert os.path.isfile(cell._file("references", cell.config["reference"]))
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert callable(cell.reader(m["name"]).read)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_file_states_its_source_and_cuts(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in cfg and key in cfg["published"]
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert cfg["limits"] and cfg["reference"]


def test_run_refuses_a_cpu_and_names_the_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "'cpu'" in p.stderr and "tpu" in p.stderr


def test_a_new_cell_is_new_files_and_entries_only():
    """The tiny benchmark beside this file adds two configurations, two
    traffic mixes and a per-layer metric as files of its own, found by the
    names in its BENCHMARK.json; it shares the runners, the references and
    the other readers with benchmark/ and edits none of them."""
    tiny = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
    with open(os.path.join(tiny, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.Cell(bench, "tiny-decoder.tiny-closed4", ROOT)
    assert cell.bench_dir == tiny
    assert cell._file("metrics", "requests_completed").startswith(tiny)
    assert cell._file("metrics", "ttft_p50_ms").startswith(
        os.path.join(ROOT, "benchmark"))
    assert cell.reader("requests_completed").read(facts={"tpot_s": [1, 2]}) == 2
    assert cell.reader("requests_completed").read(facts={}) is None
