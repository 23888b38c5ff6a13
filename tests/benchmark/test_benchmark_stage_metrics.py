"""The per-layer metrics that read the program's stage counters
(`device.stage_s`, `device.lower_s` of a request's record), each on
hand-made records whose answers are known: a median over the window's
scans, 0.0 where the stage never ran, None where there is nothing to read
(a program from before the counters, as the parent of the PR that brought
them, or a request without a device record)."""
import pytest

from benchmark_testing import REPO  # noqa: F401

from benchmark import manifest
from benchmark.harness import load_named

GB = 10 ** 9

STAGE_METRICS = {
    # metric -> (the stages it sums, its layer)
    "frame_s_per_gb": (("read", "frame"), "executor"),
    "pack_s_per_gb": (("pack",), "executor"),
    "h2d_call_s_per_gb": (("h2d",), "device_link"),
    "d2h_wait_s_per_gb": (("launch", "d2h_wait"), "device_link"),
    "fetched_copy_s_per_gb": (("merge", "collect"), "device_link"),
    "assemble_s_per_gb": (("assemble", "assemble.list", "assemble.scalar",
                           "assemble.decimal", "assemble.string",
                           "assemble.table"), "arrow_assembly"),
    "assemble_list_s_per_gb": (("assemble.list",), "arrow_assembly"),
}
# every stage a read can count, each with a power of two of its own, so
# that a sum says exactly which stages went into it
ALL_STAGES = ("parse_copybook", "plan_index", "scan", "read", "frame",
              "decode", "pack", "compile", "h2d", "launch", "d2h_wait",
              "merge", "collect", "to_arrow", "assemble", "assemble.list",
              "assemble.scalar", "assemble.decimal", "assemble.string",
              "assemble.table")
WEIGHT = {name: float(2 ** i) for i, name in enumerate(ALL_STAGES)}


def scan(scale: float, stages=ALL_STAGES, ok=True, **extra) -> dict:
    request = {"bytes": GB // 2, "ok": ok, "read_cobol_s": 0.0,
               "to_arrow_s": 0.0,
               "device": {"stage_s": {name: WEIGHT[name] * scale
                                      for name in stages},
                          "lower_s": 0.25, "compile_s": 0.5}}
    request.update(extra)
    return request


def record(window: list, warm=()) -> dict:
    return {"warm": {"requests": list(warm)},
            "window": {"requests": list(window)}}


@pytest.mark.parametrize("metric", sorted(STAGE_METRICS))
def test_stage_metric_is_the_median_of_its_stages_per_gb(metric):
    read = load_named("layer_metrics", metric).read
    stages, _ = STAGE_METRICS[metric]
    want = sum(WEIGHT[name] for name in stages)
    # three scans that held at scales 1, 2, 10 and one that failed: the
    # median is the scan at scale 2, over half a GB
    window = [scan(10.0), scan(1.0), scan(2.0), scan(100.0, ok=False)]
    value = read(record(window))
    assert isinstance(value, float)
    assert value == pytest.approx(want * 2.0 * 2)


@pytest.mark.parametrize("metric", sorted(STAGE_METRICS))
def test_stage_metric_is_zero_where_its_stages_never_ran(metric):
    read = load_named("layer_metrics", metric).read
    others = [name for name in ALL_STAGES
              if name not in STAGE_METRICS[metric][0]]
    value = read(record([scan(1.0, stages=others)]))
    assert isinstance(value, float) and value == 0.0


ALL_READERS = sorted(STAGE_METRICS) + ["host_unattributed_s_per_gb"]


@pytest.mark.parametrize("metric", ALL_READERS)
@pytest.mark.parametrize("device", [
    None,                                                  # a host read
    {"launches": {"8192x16064": 16}, "compile_s": 0.0},    # the parent
], ids=["no_device_record", "program_without_counters"])
def test_stage_metric_finds_nothing_to_read(metric, device):
    read = load_named("layer_metrics", metric).read
    window = [{"bytes": GB, "ok": True, "device": device,
               "read_cobol_s": 1.0, "to_arrow_s": 2.0}]
    assert read(record(window)) is None
    assert read(record([])) is None


def test_host_unattributed_is_the_two_calls_less_every_stage():
    read = load_named("layer_metrics", "host_unattributed_s_per_gb").read
    total = sum(WEIGHT.values())
    window = [scan(1.0, read_cobol_s=total, to_arrow_s=3.0),
              scan(1.0, read_cobol_s=total, to_arrow_s=1.0),
              scan(1.0, read_cobol_s=total, to_arrow_s=2.0)]
    value = read(record(window))
    assert isinstance(value, float)
    assert value == pytest.approx(2.0 * 2)        # 2 s over half a GB


def test_warm_lower_s_sums_the_warm_up():
    read = load_named("layer_metrics", "warm_lower_s").read
    warm = [scan(1.0), scan(1.0), {"bytes": GB, "ok": True, "device": None}]
    value = read(record([scan(1.0)], warm=warm))
    assert isinstance(value, float) and value == 0.5
    assert read(record([], warm=[{"device": {"compile_s": 1.0}}])) is None
    assert read(record([], warm=[{"device": None}])) is None


def test_the_manifest_declares_each_stage_metric_with_its_layer():
    declared = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name, (_, layer) in STAGE_METRICS.items():
        entry = declared[name]
        assert entry["layer"] == layer and entry["unit"] == "s/GB"
        assert entry["source"] == "program_span"
        assert entry["moves"] == "scan_mb_per_s"
        assert entry["better"] == "lower" and "workloads" not in entry
    assert declared["host_unattributed_s_per_gb"]["workloads"] == [
        "exp3_read", "exp1_read"]
    assert declared["warm_lower_s"]["moves"] == "setup_s"
    assert declared["warm_lower_s"]["layer"] == "device_program"
