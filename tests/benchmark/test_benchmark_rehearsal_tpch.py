"""The cell tpch_q6_q1 rehearsed on the CPU (a 298 KB file of two
generated chunks, Pallas interpreted, the device labelled cpu), traced and
untraced; its readers against a program without the query's stages and
counts; and the manifest: what PR 30 appended, and every entry that was
there as it was."""
import hashlib
import json
import os

import pytest

from benchmark_testing import check_result, declared, rehearse

from benchmark import manifest, run
from benchmark.drivers import inprocess_query

pytestmark = pytest.mark.jax
CELL = "tpch_q6_q1"
NEW = {"query_q6_s_per_gb", "query_q1_s_per_gb", "query_host_s_per_gb",
       "query_fallback_share"}
# sha256 of the manifest as PR 29 left it (json.dumps, sort_keys)
PARENT_MANIFEST = \
    "971b27d617fe60bfe7d21de2fd1831e49d47094e7034b7e056727340b47ad6aa"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_exactly_the_declared_metrics(capsys, trace):
    result, lines = rehearse(capsys, CELL, trace)
    check_result(CELL, trace, result)
    (setup, _) = [line for line in lines if line.get("phase") == "setup"]
    assert setup["generated_bytes"] == [2000 * 149]
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    # one round: Q6's 29 bytes a row and Q1's 38, one launch each
    assert warm["requests"] == 1
    assert warm["launches"] == {"2048x29": 1, "2048x38": 1}
    (window,) = [line for line in lines if line.get("phase") == "window"]
    assert set(window["launches"]) == set(warm["launches"])
    (check,) = [line for line in lines if line.get("phase") == "check"]
    assert check["failures"] == [] and check["oracle_records_per_file"] == 40
    if trace:
        metrics = result["metrics"]
        assert NEW <= set(metrics)
        assert metrics["query_fallback_share"]["value"] == 0.0
        assert metrics["query_q6_s_per_gb"]["value"] > 0
        assert metrics["query_q1_s_per_gb"]["value"] > 0
        assert metrics["query_host_s_per_gb"]["value"] > 0
        # groups come back, not rows: no table is fetched or assembled
        assert metrics["d2h_bytes_per_input_byte"]["value"] < 0.01
        assert metrics["h2d_bytes_per_input_byte"]["value"] < 0.3
        for absent in ("fetched_copy_s_per_gb", "assemble_s_per_gb",
                       "assemble_list_s_per_gb"):
            assert metrics[absent]["value"] == 0.0


def test_the_cell_declares_the_new_metrics_and_the_old_cells_do_not():
    spec = manifest.load()
    assert NEW <= set(declared(CELL, "per_layer", spec))
    for cell in ("exp3_read", "exp1_read", "exp2_read"):
        assert not NEW & set(declared(cell, "per_layer", spec))
    assert {"setup_s", "scan_mb_per_s"} <= set(declared(CELL, "end_to_end",
                                                        spec))
    everywhere = {m["name"] for m in spec["per_layer"]
                  if "workloads" not in m}
    assert {"decode_roofline", "device_idle_share", "pack_s_per_gb",
            "d2h_bytes_per_input_byte"} <= everywhere
    assert everywhere <= set(declared(CELL, "per_layer", spec))


def test_a_program_without_the_query_layer_leaves_its_metrics_out():
    """The parent counts no `query.*` stage and no `query_chunks`: each
    reader returns nothing and does not raise."""
    from benchmark.layer_metrics import (query_fallback_share,
                                         query_host_s_per_gb,
                                         query_q1_s_per_gb, query_q6_s_per_gb)

    def record(**request):
        return {"window": {"requests": [dict(ok=True, bytes=2 * 10 ** 9,
                                             **request)]}}

    old = record(device={"stage_s": {"pack": 1.0}, "h2d_bytes": 5})
    for reader in (query_fallback_share, query_host_s_per_gb,
                   query_q1_s_per_gb, query_q6_s_per_gb):
        assert reader.read(old) is None
        assert reader.read(record(device=None)) is None
    new = record(
        file_bytes=10 ** 9, query_s={"q6": 0.5, "q1": 2.0},
        device={"stage_s": {"query.bind": 0.25, "query.merge": 0.5,
                            "query.fallback": 0.25, "pack": 3.0},
                "query_chunks": 8, "query_fallback_chunks": 2})
    assert query_q6_s_per_gb.read(new) == 0.5
    assert query_q1_s_per_gb.read(new) == 2.0
    assert query_host_s_per_gb.read(new) == 0.5     # 1.0 s over 2 GB
    assert query_fallback_share.read(new) == 0.25


def test_device_records_of_a_rounds_queries_add_up():
    a = {"launches": {"8x2": 1}, "h2d_bytes": 3, "compile_s": 0.5,
         "devices": ["d0"], "has_kernel": True, "interpreted": False,
         "stage_s": {"pack": 1.0}, "query_chunks": 2}
    b = {"launches": {"8x2": 2, "8x3": 1}, "h2d_bytes": 4, "compile_s": 0.25,
         "devices": ["d0"], "has_kernel": False, "interpreted": True,
         "stage_s": {"pack": 0.5, "h2d": 2.0}, "query_chunks": 3}
    assert inprocess_query.add_records(None, a) == a
    assert inprocess_query.add_records(a, b) == {
        "launches": {"8x2": 3, "8x3": 1}, "h2d_bytes": 7, "compile_s": 0.75,
        "devices": ["d0"], "has_kernel": False, "interpreted": True,
        "stage_s": {"h2d": 2.0, "pack": 1.5}, "query_chunks": 5}


def test_the_manifest_gained_one_cell_and_lost_nothing(capsys):
    spec = manifest.load()
    cell = manifest.find(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_lineitem_sf1", "inprocess_query", 1)
    config = manifest.find(spec["configs"], cell["config"], "config")
    assert config["reduced"] == [] and len(config["source"]) <= 200
    assert config["file"] == "benchmark/configs/tpch_lineitem_sf1.json"
    with open(os.path.join(manifest.ROOT, config["file"])) as f:
        assert json.load(f)["source"] == config["source"]
    for name in NEW:
        metric = manifest.find(spec["per_layer"], name, "metric")
        assert (metric["layer"], metric["moves"], metric["workloads"]) == (
            "query", "scan_mb_per_s", [CELL])
    assert manifest.problems(spec) == []
    assert run.main(["--validate"]) == 0
    assert "no problem found" in capsys.readouterr().out
    # what was there, as it was: the first entries of each list are the
    # parent's whole manifest
    parent = dict(spec, configs=spec["configs"][:3],
                  workloads=spec["workloads"][:3],
                  per_layer=spec["per_layer"][:19])
    assert hashlib.sha256(json.dumps(parent, sort_keys=True).encode()
                          ).hexdigest() == PARENT_MANIFEST
