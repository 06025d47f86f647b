"""BENCHMARK.json against its own rules: everything a cell or a metric
names exists under that name, names and units keep to the allowed
characters, every per-layer metric moves an end-to-end metric that its
cells report, and the command refuses to run off the chip."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, spread  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
BENCH = os.path.join(REPO, MANIFEST["paths"][0])
CELLS = [c["name"] for c in MANIFEST["workloads"]]
METRICS = [(g, m["name"]) for g in ("end_to_end", "per_layer")
           for m in MANIFEST[g]]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    n = len(CELLS)
    four = sum(c["chips"] == 4 for c in MANIFEST["workloads"])
    assert four <= max(1, n // 4)
    assert all(c["chips"] in (1, 4) for c in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_names_files_that_exist(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == cell["config"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(MANIFEST["paths"][0] + "/")
    config = _json(REPO, entry["file"])
    assert config["reduced"] == entry["reduced"]
    assert 1 <= len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    importlib.import_module(f"benchmark.runners.{config['runner']}").Runner
    harness_path = config["builder"].rpartition(".")
    assert hasattr(importlib.import_module(harness_path[0]),
                   harness_path[2])
    traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["who"] and traffic["why"]
    importlib.import_module(
        f"benchmark.generators.{traffic['generator']}").make
    # setup_s, another end-to-end metric, and a per-layer metric
    e2e = [m["name"] for m in harness.cell_metrics(
        MANIFEST, "end_to_end", cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(MANIFEST, "per_layer", cell["name"])


def test_every_configuration_is_used_and_has_its_own_file():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    assert {c["name"] for c in MANIFEST["configs"]} == {
        c["config"] for c in MANIFEST["workloads"]}
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("group,name", METRICS)
def test_metric_is_well_formed_and_has_a_reader(group, name):
    metric = next(m for m in MANIFEST[group] if m["name"] == name)
    assert NAME.match(name) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    spec = _json(BENCH, harness.METRIC_DIRS[group], name + ".json")
    importlib.import_module(f"benchmark.readers.{spec['reader']}").read
    if group == "end_to_end":
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert 1 <= len(metric["layer"]) <= 200
        moved = next(m for m in MANIFEST["end_to_end"]
                     if m["name"] == metric["moves"])
        # the metric it moves is reported in every cell where this one is
        assert all(_reports(moved, c) for c in CELLS
                   if _reports(metric, c))
        if name.endswith("_roofline") or "mfu" in name:
            assert metric["unit"] == "%"


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_bound_is_a_whole_number_of_half_per_cents(metric):
    # spread.bound_for's grid: five times the widest spread any set
    # read, to the nearest half per cent, from 1% to 10%
    steps = metric["bound"] / spread.BOUND_STEP
    assert steps == pytest.approx(round(steps), abs=1e-9)
    assert spread.BOUND_MIN <= metric["bound"] <= spread.BOUND_MAX


def test_metric_names_are_unique_and_every_file_is_named():
    names = [n for _, n in METRICS]
    assert len(set(names)) == len(names)
    for group, directory in harness.METRIC_DIRS.items():
        on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH,
                                                           directory))}
        assert on_disk == {m["name"] for m in MANIFEST[group]}
    # a layer is written the same way wherever it is named, and PERF.md
    # lists it
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in MANIFEST["per_layer"]}:
        assert f"| {layer} " in perf, layer


def test_harness_and_runners_name_no_cell_configuration_or_mix():
    banned = ({c["name"] for c in MANIFEST["workloads"]}
              | {c["name"] for c in MANIFEST["configs"]}
              | {c["traffic"] for c in MANIFEST["workloads"]})
    for path in ("run.py", "harness.py", "runners/train.py",
                 "runners/serve.py", "runners/common.py"):
        with open(os.path.join(BENCH, path)) as f:
            src = f.read()
        for name in banned:
            assert not re.search(rf"\b{re.escape(name)}\b", src), (
                path, name)
        for word in ("subprocess", "multiprocessing", "JAX_PLATFORMS",
                     "jax_platforms", "BENCH_RUN"):
            assert word not in src, (path, word)


def test_run_py_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""            # no result line
