"""The cell hier_companies_read rehearsed on the CPU (a 256 KiB file of
two chunks, Pallas interpreted, the device labelled cpu), traced and
untraced; the two new readers on a program without the stage and the
counters; and the manifest with the new cell in it and every earlier
entry as it was."""
import hashlib
import json
import os

import pytest

from benchmark_testing import check_result, declared, rehearse

from benchmark import manifest, run

pytestmark = pytest.mark.jax
CELL = "hier_companies_read"
NEW = {"assemble_hier_s_per_gb": "arrow_assembly",
       "hier_fallback_share": "executor"}
# sha256 of the parent's BENCHMARK.json (PR 36's), keys sorted
PARENT_MANIFEST = \
    "9e8f95f1f1b5f0ca6e5b2019b794296e297c02b24e129c1c02e90a2d983c4d04"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_exactly_the_declared_metrics(capsys, trace):
    result, lines = rehearse(capsys, CELL, trace)
    check_result(CELL, trace, result)
    (setup, _) = [line for line in lines if line.get("phase") == "setup"]
    assert abs(sum(setup["generated_bytes"]) - (256 << 10)) < 32768
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    (window,) = [line for line in lines if line.get("phase") == "window"]
    # every record crosses the link at the widest segment's extent
    assert warm["launches"] and all(shape.endswith("x108")
                                    for shape in warm["launches"])
    assert set(window["launches"]) <= set(warm["launches"])
    (check,) = [line for line in lines if line.get("phase") == "check"]
    assert check["failures"] == [] and check["oracle_records_per_file"] == 64
    if trace:
        metrics = result["metrics"]
        assert set(NEW) <= set(metrics)
        assert metrics["hier_fallback_share"]["value"] == 0.0
        assert metrics["assemble_hier_s_per_gb"]["value"] > 0
        assert (metrics["assemble_s_per_gb"]["value"]
                >= metrics["assemble_hier_s_per_gb"]["value"])
        assert metrics["assemble_list_s_per_gb"]["value"] == 0.0


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """The parent has no stage `assemble.hier` and counts no `hier_*`:
    each reader returns nothing and does not raise."""
    from benchmark.layer_metrics import (assemble_hier_s_per_gb,
                                         hier_fallback_share)

    def record(**request):
        return {"window": {"requests": [dict(ok=True, bytes=2 * 10 ** 9,
                                             **request)]}}

    old = record(device={"stage_s": {"pack": 1.0, "assemble.table": 2.0},
                         "h2d_bytes": 5})
    for reader in (assemble_hier_s_per_gb, hier_fallback_share):
        assert reader.read(old) is None
        assert reader.read(record(device=None)) is None
    new = record(device={
        "stage_s": {"assemble.hier": 0.5, "assemble.hier.assign": 1.0,
                    "assemble.hier.leaves": 2.0, "assemble.string": 4.0},
        "hier_roots": 30, "hier_row_path_roots": 10})
    assert assemble_hier_s_per_gb.read(new) == 1.75     # 3.5 s over 2 GB
    assert hier_fallback_share.read(new) == 0.25


def test_the_manifest_gained_one_cell_and_lost_nothing(capsys):
    spec = manifest.load()
    cell = manifest.find(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hier_companies_test17", "inprocess_scan_proved", 1)
    config = manifest.find(spec["configs"], cell["config"], "config")
    assert config["reduced"] == ["file_bytes"]
    assert len(config["source"]) <= 200
    assert config["file"] == "benchmark/configs/hier_companies_test17.json"
    with open(os.path.join(manifest.ROOT, config["file"])) as f:
        stated = json.load(f)
    assert stated["source"] == config["source"]
    assert stated["reduced"] == ["file_bytes"] and stated["assumed"]
    assert len(stated["guarantees"]) == 6
    assert stated["full"] == {"file_bytes": 512 << 20,
                              "generate_chunk_bytes": 32 << 20,
                              "oracle_sample_records": 8192}
    for name, layer in NEW.items():
        metric = manifest.find(spec["per_layer"], name, "metric")
        assert (metric["layer"], metric["moves"], metric["workloads"]) == (
            layer, "scan_mb_per_s", [CELL])
    assert {"setup_s", "scan_mb_per_s"} == set(declared(CELL, "end_to_end",
                                                        spec))
    everywhere = {m["name"] for m in spec["per_layer"]
                  if "workloads" not in m}
    assert {"decode_roofline", "device_idle_share", "pack_s_per_gb",
            "h2d_bytes_per_input_byte", "d2h_bytes_per_input_byte",
            "assemble_s_per_gb"} <= everywhere
    assert everywhere <= set(declared(CELL, "per_layer", spec))
    assert manifest.problems(spec) == []
    assert run.main(["--validate"]) == 0
    assert "no problem found" in capsys.readouterr().out
    # what was there, as it was: the first entries of each list are the
    # parent's whole manifest
    parent = dict(spec, configs=spec["configs"][:5],
                  workloads=spec["workloads"][:5],
                  per_layer=spec["per_layer"][:33])
    assert hashlib.sha256(json.dumps(parent, sort_keys=True).encode()
                          ).hexdigest() == PARENT_MANIFEST


def test_pr36s_entry_stays_as_its_own_test_pinned_it():
    """`test_benchmark_preframed_share.py::test_the_manifest_declares_the_
    metric` also asserts that `preframed_shard_share` is the LAST
    `per_layer` entry, which stopped being so with the first metric
    appended behind it (tests/conftest.py marks it for that). Its other
    assertions, held here: the entry to the letter, directly behind PR
    34's last metric, in a manifest with no problem."""
    spec = manifest.load()
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index("preframed_shard_share")
    assert names.count("preframed_shard_share") == 1
    assert spec["per_layer"][at] == {
        "name": "preframed_shard_share", "unit": "share",
        "better": "higher", "source": "program_counter",
        "layer": "executor", "moves": "scan_mb_per_s",
        "workloads": ["exp3_read", "exp2_read", "tpch_orders_odo_read"]}
    assert names[at - 1] == "plan_index_s_per_gb"
    assert manifest.problems(spec) == []
