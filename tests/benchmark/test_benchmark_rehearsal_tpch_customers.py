"""The cell tpch_customers_odo_read rehearsed on the CPU (a 256 KiB file
of two chunks, Pallas interpreted, the device labelled cpu), traced and
untraced; its three readers on a program without the stages and the
counters; and the manifest with the new cell in it and every earlier
entry as it was."""
import hashlib
import json
import os

import pytest

from benchmark_testing import check_result, declared, rehearse

from benchmark import manifest, run

pytestmark = pytest.mark.jax
CELL = "tpch_customers_odo_read"
NEW = {"element_frame_s_per_gb": "executor",
       "assemble_nested_s_per_gb": "arrow_assembly",
       "odo_nested_fallback_share": "executor"}
# sha256 of the parent's BENCHMARK.json (PR 39's), keys sorted
PARENT_MANIFEST = \
    "2941fd978f5b6a3ec043dd786f33ba985942d1311ff43091e11e5a152b3d91cd"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_exactly_the_declared_metrics(capsys, trace):
    result, lines = rehearse(capsys, CELL, trace)
    check_result(CELL, trace, result)
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    (window,) = [line for line in lines if line.get("phase") == "window"]
    # the two row kinds, each at its own width
    assert {shape.split("x")[1] for shape in warm["launches"]} == {
        "224", "1149"}
    assert set(window["launches"]) <= set(warm["launches"])
    (check,) = [line for line in lines if line.get("phase") == "check"]
    assert check["failures"] == [] and check["oracle_records_per_file"] == 24
    if trace:
        metrics = result["metrics"]
        assert set(NEW) <= set(metrics)
        assert metrics["odo_nested_fallback_share"]["value"] == 0.0
        assert metrics["element_frame_s_per_gb"]["value"] > 0
        assert (metrics["frame_s_per_gb"]["value"]
                >= metrics["element_frame_s_per_gb"]["value"])
        assert (metrics["assemble_list_s_per_gb"]["value"]
                >= metrics["assemble_nested_s_per_gb"]["value"])


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """The parent walks these records on the host: no stage
    `frame.elements` or `assemble.list.nested`, no `odo_nested_*` count.
    Each reader returns nothing and does not raise."""
    from benchmark.layer_metrics import (assemble_nested_s_per_gb,
                                         element_frame_s_per_gb,
                                         odo_nested_fallback_share)

    readers = (element_frame_s_per_gb, assemble_nested_s_per_gb,
               odo_nested_fallback_share)

    def record(**request):
        return {"window": {"requests": [dict(ok=True, bytes=2 * 10 ** 9,
                                             **request)]}}

    old = record(device={"stage_s": {"pack": 1.0, "assemble.list": 2.0},
                         "odo_records": 7, "odo_fallback_records": 0})
    for reader in readers:
        assert reader.read(old) is None
        assert reader.read(record(device=None)) is None
    new = record(device={
        "stage_s": {"frame": 0.25, "frame.elements": 1.0,
                    "assemble.list": 3.0, "assemble.list.nested": 0.5},
        "odo_nested_records": 30, "odo_nested_fallback_records": 10})
    assert element_frame_s_per_gb.read(new) == 0.5     # 1 s over 2 GB
    assert assemble_nested_s_per_gb.read(new) == 0.25
    assert odo_nested_fallback_share.read(new) == 0.25


def test_the_manifest_gained_one_cell_and_lost_nothing(capsys):
    spec = manifest.load()
    cell = manifest.find(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_customers_nested", "inprocess_scan_proved", 1)
    config = manifest.find(spec["configs"], cell["config"], "config")
    assert config["reduced"] == ["rows"]
    assert config["file"] == "benchmark/configs/tpch_customers_nested.json"
    with open(os.path.join(manifest.ROOT, config["file"])) as f:
        stated = json.load(f)
    assert stated["source"] == config["source"]
    assert stated["reduced"] == ["rows"] and stated["assumed"]
    assert stated["full"] == {"file_bytes": 512 << 20,
                              "generate_chunk_bytes": 32 << 20,
                              "oracle_sample_records": 4096}
    for name, layer in NEW.items():
        metric = manifest.find(spec["per_layer"], name, "metric")
        assert (metric["layer"], metric["moves"], metric["workloads"]) == (
            layer, "scan_mb_per_s", [CELL])
    assert {"setup_s", "scan_mb_per_s"} == set(declared(CELL, "end_to_end",
                                                        spec))
    everywhere = {m["name"] for m in spec["per_layer"]
                  if "workloads" not in m}
    assert everywhere <= set(declared(CELL, "per_layer", spec))
    assert manifest.problems(spec) == []
    assert run.main(["--validate"]) == 0
    assert "no problem found" in capsys.readouterr().out
    # what was there, as it was: the first entries of each list are the
    # parent's whole manifest
    parent = dict(spec, configs=spec["configs"][:6],
                  workloads=spec["workloads"][:6],
                  per_layer=spec["per_layer"][:35])
    assert hashlib.sha256(json.dumps(parent, sort_keys=True).encode()
                          ).hexdigest() == PARENT_MANIFEST
