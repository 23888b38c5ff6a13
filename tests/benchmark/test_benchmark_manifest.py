"""BENCHMARK.json against the driver's rules of spelling and shape, and
the requirement that the harness is driven by data: a new cell,
configuration, traffic mix and per-layer metric are new files and new
manifest entries, and no edit of a file that is there."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark_testing import REPO

from benchmark import manifest

SPEC = manifest.load()
BENCH = os.path.join(REPO, "benchmark")


def identifiers():
    out = []
    for cell in SPEC["workloads"]:
        out += [("cell", cell["name"]), ("config", cell["config"]),
                ("traffic", cell["traffic"])]
    for config in SPEC["configs"]:
        out.append(("config", config["name"]))
        out += [("reduced", key) for key in config["reduced"]]
    for metric in SPEC["end_to_end"] + SPEC["per_layer"]:
        out.append(("metric", metric["name"]))
    out += [("layer", metric["layer"]) for metric in SPEC["per_layer"]]
    return sorted(set(out))


def test_the_manifest_has_no_problem():
    assert manifest.problems(SPEC) == []


@pytest.mark.parametrize("kind,name", identifiers())
def test_identifier_is_spelled_as_the_driver_takes_it(kind, name):
    """1 to 64 letters, digits, '_', '.', '-', starting with a letter, a
    digit or '_': for cells, configurations, traffic, metrics AND layers
    (PR 22 was refused for a layer written as prose)."""
    assert manifest.NAME.match(name), (kind, name)


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"] + SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    cells = {cell["name"] for cell in SPEC["workloads"]}
    assert manifest.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(manifest.cells_of(metric, SPEC)) <= cells
    folder = "layer_metrics" if "layer" in metric else "end_to_end"
    assert os.path.isfile(os.path.join(BENCH, folder,
                                       metric["name"] + ".py"))
    if "layer" in metric:
        (moved,) = [m for m in SPEC["end_to_end"]
                    if m["name"] == metric["moves"]]
        assert set(manifest.cells_of(metric, SPEC)) <= set(
            manifest.cells_of(moved, SPEC))
    else:
        assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    (config,) = [c for c in SPEC["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(REPO, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"] and len(body["source"]) <= 200
    assert body["reduced"] == config["reduced"]
    assert body["guarantees"] and body["assumed"]
    traffic = manifest.load_json("traffic", cell["traffic"] + ".json")
    for kind, name in (("drivers", traffic["driver"]),
                       ("generators", body["generator"])):
        assert os.path.isfile(os.path.join(BENCH, kind, name + ".py"))
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def test_shape_of_the_whole():
    four = [c for c in SPEC["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    assert isinstance(SPEC["run_seconds"], int)
    assert 10 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"] == ["python3", "benchmark/run.py"]


def broken(change):
    spec = copy.deepcopy(SPEC)
    change(spec)
    return manifest.problems(spec)


@pytest.mark.parametrize("change", [
    lambda s: s["per_layer"][0].update(layer="executor (engine/pipeline)"),
    lambda s: s["per_layer"][0].update(moves="request_p95_s"),
    lambda s: s["per_layer"][0].update(why="a key the contract lacks"),
    lambda s: s["end_to_end"][1].update(unit="MB per second"),
    lambda s: s["end_to_end"][1].update(bound=0.5),
    lambda s: s["workloads"][0].update(name="exp3 read"),
    lambda s: [c.update(chips=4) for c in s["workloads"]],
    lambda s: s.update(run_seconds=52),
    lambda s: s["configs"][0].update(source="x" * 201),
    lambda s: s["workloads"][0].update(traffic="no_such_traffic"),
], ids=["layer_as_prose", "moves_a_metric_its_cells_lack", "extra_key",
        "unit_with_spaces", "bound_too_wide", "name_with_space",
        "too_many_four_chip_cells", "run_seconds", "long_source",
        "missing_traffic_file"])
def test_what_the_driver_refuses_is_found(change):
    assert broken(change)


THROWAWAY_READER = '''"""Rows per scan: a throw-away counter for the test."""
from ..harness import completed, median


def read(record):
    return float(median([r["rows"] for r in completed(record)]))
'''


def test_new_cell_config_traffic_and_metric_are_new_files_only(tmp_path):
    """In a copy of the benchmark: one new file each for a configuration,
    a traffic mix and a per-layer metric, one new manifest entry each and
    a cell over them - and run.py, unchanged, runs the new cell."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    config = manifest.load_json("configs", "exp3_multiseg_wide.json")
    config.update(name="throwaway_config", source="a throw-away source")
    config["rehearse"]["file_bytes"] = 2 << 20
    (root / "benchmark/configs/throwaway_config.json").write_text(
        json.dumps(config))
    traffic = manifest.load_json("traffic", "inprocess_scan.json")
    traffic["rehearse"]["files"] = 2
    (root / "benchmark/traffic/throwaway_traffic.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/layer_metrics/throwaway_rows.py").write_text(
        THROWAWAY_READER)
    spec = copy.deepcopy(SPEC)
    spec["paths"] = ["benchmark"]
    spec["configs"].append({
        "name": "throwaway_config", "source": config["source"],
        "file": "benchmark/configs/throwaway_config.json",
        "reduced": ["file_bytes"], "why": "a test"})
    spec["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway_config",
        "traffic": "throwaway_traffic", "chips": 1, "why": "a test"})
    spec["per_layer"].append({
        "name": "throwaway_rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "scan_mb_per_s", "workloads": ["throwaway_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert manifest.problems(spec, str(root)) == []

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               TF_CPP_MIN_LOG_LEVEL="3")
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark/run.py"), "--workload",
         "throwaway_cell", "--seed", "7", "--seconds", "1", "--trace", "1",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=300, cwd=str(root))
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] and result["attempted"] >= 2
    assert result["metrics"]["throwaway_rows"]["value"] > 0
    assert "read_call_s_per_gb" not in result["metrics"]  # not its cell
    assert "h2d_bytes_per_input_byte" in result["metrics"]  # every cell
    for path, body in before.items():
        assert path.read_bytes() == body, f"{path} was edited"
