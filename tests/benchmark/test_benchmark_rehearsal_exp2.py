"""The cell exp2_read rehearsed on the CPU (a 256 KiB file of two chunks,
Pallas interpreted, the device labelled cpu), traced and untraced, and the
manifest with the new cell in it."""
import pytest

from benchmark_testing import check_result, declared, rehearse

from benchmark import manifest, run

pytestmark = pytest.mark.jax
CELL = "exp2_read"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_exactly_the_declared_metrics(capsys, trace):
    result, lines = rehearse(capsys, CELL, trace)
    check_result(CELL, trace, result)
    (setup, _) = [line for line in lines if line.get("phase") == "setup"]
    assert abs(sum(setup["generated_bytes"]) - (256 << 10)) < 4096
    (window,) = [line for line in lines if line.get("phase") == "window"]
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    # the plan's extent is the 64 B payload; the bucket is the program's
    assert warm["launches"] and all(shape.endswith("x64")
                                    for shape in warm["launches"])
    # the window launched only shapes the warm-up had launched
    assert set(window["launches"]) <= set(warm["launches"])
    (check,) = [line for line in lines if line.get("phase") == "check"]
    assert check["failures"] == [] and check["oracle_records_per_file"] == 64
    if trace:
        new = {"seg_id_s_per_gb", "assemble_string_s_per_gb"}
        assert new <= set(result["metrics"])
        assert result["metrics"]["seg_id_s_per_gb"]["value"] > 0
        assert result["metrics"]["assemble_string_s_per_gb"]["value"] > 0
        assert result["metrics"]["assemble_list_s_per_gb"]["value"] == 0.0


def test_the_cell_declares_the_new_metrics_and_the_old_cells_do_not():
    spec = manifest.load()
    new = {"seg_id_s_per_gb", "assemble_string_s_per_gb"}
    assert new <= set(declared(CELL, "per_layer", spec))
    for cell in ("exp3_read", "exp1_read"):
        assert not new & set(declared(cell, "per_layer", spec))
    assert {"setup_s", "scan_mb_per_s"} <= set(declared(CELL, "end_to_end",
                                                        spec))
    # every metric that lists no cells is reported here too
    everywhere = {m["name"] for m in spec["per_layer"]
                  if "workloads" not in m}
    assert everywhere <= set(declared(CELL, "per_layer", spec))


def test_a_program_without_the_seg_id_stage_leaves_the_metric_out():
    """The parent's program counts no stage `seg_id`: the reader returns
    nothing and the line leaves the metric out."""
    from benchmark.layer_metrics import assemble_string_s_per_gb
    from benchmark.layer_metrics import seg_id_s_per_gb

    def record(stage_s):
        scan = {"ok": True, "bytes": 10 ** 9, "device": {"stage_s": stage_s}}
        return {"window": {"requests": [scan]}}

    old = record({"assemble.string": 2.0, "frame": 1.0})
    assert seg_id_s_per_gb.read(old) is None
    assert assemble_string_s_per_gb.read(old) == 2.0
    new = record({"seg_id": 0.25, "assemble.seg_id": 0.5, "frame": 1.0})
    assert seg_id_s_per_gb.read(new) == 0.75
    assert seg_id_s_per_gb.read({"window": {"requests": [
        {"ok": True, "bytes": 1, "device": None}]}}) is None


def test_the_manifest_holds_with_the_new_cell(capsys):
    """Membership and shape only: the next cell or configuration that is
    appended leaves this test as it is."""
    spec = manifest.load()
    cell = manifest.find(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "exp2_multiseg_narrow", "inprocess_scan", 1)
    config = manifest.find(spec["configs"], cell["config"], "config")
    assert config["reduced"] == ["file_bytes"]
    assert config["file"] == "benchmark/configs/exp2_multiseg_narrow.json"
    assert {"exp3_read", "exp1_read"} <= {c["name"]
                                          for c in spec["workloads"]}
    assert manifest.problems(spec) == []
    assert run.main(["--validate"]) == 0
    assert "no problem found" in capsys.readouterr().out
