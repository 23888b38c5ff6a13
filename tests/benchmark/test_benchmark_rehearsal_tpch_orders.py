"""The cell tpch_orders_odo_read rehearsed on the CPU (a 256 KiB file of
two chunks, Pallas interpreted, the device labelled cpu), traced and
untraced; the proved driver stopping in `set_up` when the read launches
nothing; the new readers on a program without the counters; and the
manifest with the new cell in it and every earlier entry as it was."""
import hashlib
import json
import os

import pytest

from benchmark_testing import check_result, declared, rehearse

from benchmark import inputs, manifest, run
from benchmark.harness import BenchFault, Run, Tracer, load_named

pytestmark = pytest.mark.jax
CELL = "tpch_orders_odo_read"
NEW = {"odo_fallback_share": "executor", "odo_expand_s_per_gb": "executor",
       "assemble_list_slots_s_per_gb": "arrow_assembly"}
# sha256 of the parent's BENCHMARK.json (PR 31's), keys sorted
PARENT_MANIFEST = \
    "66749a15a49f4e6afa3d68cdbc6139e81fa2b31e01cdcaf16dea372f36bd80b5"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_exactly_the_declared_metrics(capsys, trace):
    result, lines = rehearse(capsys, CELL, trace)
    check_result(CELL, trace, result)
    (setup, _) = [line for line in lines if line.get("phase") == "setup"]
    assert abs(sum(setup["generated_bytes"]) - (256 << 10)) < 4096
    (warm,) = [line for line in lines if line.get("phase") == "warm_up"]
    (window,) = [line for line in lines if line.get("phase") == "window"]
    # rows cross the link compact, padded to the plan's extent
    assert warm["launches"] and all(shape.endswith("x1153")
                                    for shape in warm["launches"])
    assert set(window["launches"]) <= set(warm["launches"])
    (check,) = [line for line in lines if line.get("phase") == "check"]
    assert check["failures"] == [] and check["oracle_records_per_file"] == 48
    if trace:
        metrics = result["metrics"]
        assert set(NEW) <= set(metrics)
        assert metrics["odo_fallback_share"]["value"] == 0.0
        assert metrics["assemble_list_slots_s_per_gb"]["value"] == 0.0
        assert metrics["odo_expand_s_per_gb"]["value"] > 0
        assert metrics["assemble_list_s_per_gb"]["value"] > 0


def make_run(tmp_path, **reader_options) -> Run:
    spec = manifest.load()
    cell = manifest.find(spec["workloads"], CELL, "workload")
    config = manifest.load_json("configs", cell["config"] + ".json")
    config["reader_options"].update(reader_options)
    traffic = manifest.load_json("traffic", cell["traffic"] + ".json")
    made = Run(cell=cell, config=config, traffic=traffic, seed=2147483777,
               seconds=1.0, trace=False, rehearse=True,
               workdir=str(tmp_path), out_dir=str(tmp_path))
    made.generator = load_named("generators", config["generator"])
    made.files = inputs.make(config, traffic, made.workdir, made.seed,
                             made.scale)
    made.device = run.find_device(1, rehearse=True)
    made.tracer = Tracer(False, made.out_dir)
    return made


def test_the_proved_driver_stops_in_set_up_when_nothing_launches(tmp_path):
    """Host kernels stand in for a program that walks the records on the
    host, as the parent does on any backend: the run ends in `set_up`,
    before a warm-up is spent."""
    made = make_run(tmp_path, backend="numpy")
    driver = load_named("drivers", "inprocess_scan_proved").Driver(made)
    with pytest.raises(BenchFault, match="no device launch"):
        driver.set_up()
    assert not os.path.exists(os.path.join(made.workdir,
                                           "device_proof.dat"))


def test_the_proved_driver_is_inprocess_scan_after_its_proof(tmp_path):
    from benchmark.drivers import inprocess_scan, inprocess_scan_proved

    proved = inprocess_scan_proved.Driver
    assert issubclass(proved, inprocess_scan.Driver)
    assert {name for name in vars(proved) if not name.startswith("__")} \
        == {"set_up"}
    made = make_run(tmp_path)
    assert made.traffic["proof_records"] == 480
    assert made.traffic["callers"] == 1
    driver = proved(made)
    driver.set_up()                 # the cell's options reach the device
    (request,) = driver.warm_up()
    assert request["ok"], request["error"]
    assert request["device"]["odo_records"] == request["rows"]
    assert request["device"]["odo_fallback_records"] == 0
    driver.close()


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """The parent counts no `odo_*` and no stage `expand`: each reader
    returns nothing and does not raise."""
    from benchmark.layer_metrics import (assemble_list_slots_s_per_gb,
                                         odo_expand_s_per_gb,
                                         odo_fallback_share)

    def record(**request):
        return {"window": {"requests": [dict(ok=True, bytes=2 * 10 ** 9,
                                             **request)]}}

    old = record(device={"stage_s": {"pack": 1.0}, "h2d_bytes": 5})
    assert odo_fallback_share.read(old) is None
    assert odo_expand_s_per_gb.read(old) is None
    assert assemble_list_slots_s_per_gb.read(old) == 0.0
    for reader in (odo_fallback_share, odo_expand_s_per_gb,
                   assemble_list_slots_s_per_gb):
        assert reader.read(record(device=None)) is None
    new = record(device={
        "stage_s": {"expand": 0.5, "assemble.list": 3.0,
                    "assemble.list.slots": 1.0},
        "odo_records": 30, "odo_fallback_records": 10})
    assert odo_fallback_share.read(new) == 0.25
    assert odo_expand_s_per_gb.read(new) == 0.25        # 0.5 s over 2 GB
    assert assemble_list_slots_s_per_gb.read(new) == 0.5


def test_the_manifest_gained_one_cell_and_lost_nothing(capsys):
    spec = manifest.load()
    cell = manifest.find(spec["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_orders_nested", "inprocess_scan_proved", 1)
    config = manifest.find(spec["configs"], cell["config"], "config")
    assert config["reduced"] == ["rows"] and len(config["source"]) <= 200
    assert config["file"] == "benchmark/configs/tpch_orders_nested.json"
    with open(os.path.join(manifest.ROOT, config["file"])) as f:
        assert json.load(f)["source"] == config["source"]
    for name, layer in NEW.items():
        metric = manifest.find(spec["per_layer"], name, "metric")
        assert (metric["layer"], metric["moves"], metric["workloads"]) == (
            layer, "scan_mb_per_s", [CELL])
    assert {"setup_s", "scan_mb_per_s"} == set(declared(CELL, "end_to_end",
                                                        spec))
    # every metric that lists no cells is reported here too, the
    # roofline among them: the expansion is part of the one program
    everywhere = {m["name"] for m in spec["per_layer"]
                  if "workloads" not in m}
    assert {"decode_roofline", "device_idle_share", "pack_s_per_gb",
            "h2d_bytes_per_input_byte"} <= everywhere
    assert everywhere <= set(declared(CELL, "per_layer", spec))
    assert manifest.problems(spec) == []
    assert run.main(["--validate"]) == 0
    assert "no problem found" in capsys.readouterr().out
    # what was there, as it was: the first entries of each list are the
    # parent's whole manifest
    parent = dict(spec, configs=spec["configs"][:4],
                  workloads=spec["workloads"][:4],
                  per_layer=spec["per_layer"][:23])
    assert hashlib.sha256(json.dumps(parent, sort_keys=True).encode()
                          ).hexdigest() == PARENT_MANIFEST
