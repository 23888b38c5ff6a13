"""The per-layer metrics that open up the link home and `plan_index`
(PR 34): the two halves of `d2h_wait` as stages, the rate a fetching
thread sees, the link's busy time, the share of fetched bytes that did
not arrive C-contiguous, and `plan_index` with its children. Each reader
on hand-made records whose answers are known, on records with nothing to
read (no device record, a program from before the counters, a zero
denominator), on a real device read on the CPU, and as the manifest
declares it."""
import pytest

from benchmark_testing import REPO  # noqa: F401

from benchmark import manifest
from benchmark.harness import load_named

GB = 10 ** 9
READS = ["exp3_read", "exp1_read", "exp2_read", "tpch_orders_odo_read"]
RDW = ["exp3_read", "exp2_read", "tpch_orders_odo_read"]

# metric -> (unit, better, source, layer, the cells it lists or None)
NEW_METRICS = {
    "device_ready_wait_s_per_gb": ("s/GB", "lower", "program_span",
                                   "device_link", None),
    "d2h_copy_s_per_gb": ("s/GB", "lower", "program_span", "device_link",
                          None),
    "d2h_copy_gb_per_s": ("GB/s", "higher", "program_span", "device_link",
                          READS),
    "d2h_link_busy_s_per_gb": ("s/GB", "lower", "program_span",
                               "device_link", READS),
    "d2h_strided_share": ("share", "lower", "program_counter",
                          "device_link", READS),
    "plan_index_s_per_gb": ("s/GB", "lower", "program_span", "executor",
                            RDW),
}


def scan(scale: float = 1.0, ok: bool = True, **device) -> dict:
    """One scan of half a GB whose fetches: waited 3 s and copied 2 s on
    the stage clock; sat through 8 thread-seconds of the copy, the link
    busy for 4; brought 6 GB home, 1.5 GB of it strided; all times
    `scale`."""
    record = {
        "stage_s": {"launch": 0.25 * scale, "d2h_wait": 0.5 * scale,
                    "d2h_wait.ready": 3.0 * scale,
                    "d2h_wait.copy": 2.0 * scale,
                    "plan_index": 0.125 * scale,
                    "plan_index.scan": 1.0 * scale,
                    "plan_index.seg_ids": 0.5 * scale, "pack": 64.0},
        "d2h_bytes": 6 * GB, "d2h_strided_bytes": 3 * GB // 2,
        "d2h_copy_thread_s": 8.0 * scale, "d2h_copy_busy_s": 4.0 * scale}
    record.update(device)
    return {"bytes": GB // 2, "ok": ok, "device": record}


def record(*window) -> dict:
    return {"warm": {"requests": []}, "window": {"requests": list(window)}}


def read(metric: str, *window):
    return load_named("layer_metrics", metric).read(record(*window))


# three scans that held, at scales 1, 2 and 10, and one that failed: a
# median is the scan at scale 2 over half a GB; a ratio of sums takes the
# three that held (13 times the seconds of one, 3 times its bytes)
WINDOW = (scan(10.0), scan(1.0), scan(2.0), scan(100.0, ok=False))


@pytest.mark.parametrize("metric,want", [
    ("device_ready_wait_s_per_gb", 3.0 * 2 * 2),
    ("d2h_copy_s_per_gb", 2.0 * 2 * 2),
    ("d2h_link_busy_s_per_gb", 4.0 * 2 * 2),
    ("plan_index_s_per_gb", (0.125 + 1.0 + 0.5) * 2 * 2),
    ("d2h_copy_gb_per_s", 3 * 6.0 / (13 * 8.0)),
    ("d2h_strided_share", 0.25),
])
def test_reader_gives_the_known_answer(metric, want):
    value = read(metric, *WINDOW)
    assert isinstance(value, float)
    assert value == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
@pytest.mark.parametrize("device", [
    None,                                                    # a host read
    {"launches": {"8192x16064": 16}, "compile_s": 0.0},      # before PR 24
], ids=["no_device_record", "program_without_counters"])
def test_reader_finds_nothing_to_read(metric, device):
    request = {"bytes": GB, "ok": True, "device": device}
    assert read(metric, request) is None
    assert read(metric) is None
    assert read(metric, scan(ok=False)) is None


# the parent of PR 34: stages and link bytes, but neither the child
# stages nor the link's own counts
PARENT = {"stage_s": {"launch": 0.25, "d2h_wait": 5.5, "plan_index": 1.625,
                      "pack": 64.0},
          "d2h_bytes": 6 * GB, "h2d_bytes": 5 * GB}


@pytest.mark.parametrize("metric", sorted(set(NEW_METRICS)
                                          - {"plan_index_s_per_gb"}))
def test_a_scan_from_before_the_counters_leaves_the_metric_out(metric):
    request = {"bytes": GB // 2, "ok": True, "device": PARENT}
    assert read(metric, request) is None


def test_plan_index_reads_the_parents_one_stage_as_the_same_seconds():
    request = {"bytes": GB // 2, "ok": True, "device": PARENT}
    assert read("plan_index_s_per_gb", request) == pytest.approx(1.625 * 2)
    # a read that plans no index (a fixed-length file) reads 0.0
    fixed = scan()
    fixed["device"]["stage_s"] = {"pack": 1.0}
    value = read("plan_index_s_per_gb", fixed)
    assert isinstance(value, float) and value == 0.0


@pytest.mark.parametrize("metric,zeroed", [
    ("d2h_copy_gb_per_s", {"d2h_copy_thread_s": 0.0}),
    ("d2h_strided_share", {"d2h_bytes": 0, "d2h_strided_bytes": 0}),
])
def test_a_zero_denominator_leaves_the_metric_out(metric, zeroed):
    assert read(metric, scan(**zeroed)) is None
    # all bytes C-contiguous is a share of 0.0, not nothing
    if metric == "d2h_strided_share":
        value = read(metric, scan(d2h_strided_bytes=0))
        assert isinstance(value, float) and value == 0.0


def test_the_split_adds_up_to_d2h_wait_scan_by_scan():
    """`d2h_wait_s_per_gb` sums `launch`, `d2h_wait` and the stages
    beneath it: with the two children it reads what it read of the one
    block, and the children are the part of it that is not `launch` and
    the block's own few microseconds."""
    for s in (1.0, 2.0, 10.0):
        whole = read("d2h_wait_s_per_gb", scan(s))
        assert whole == pytest.approx((0.25 + 0.5 + 3.0 + 2.0) * s * 2)
        assert whole == pytest.approx(
            read("device_ready_wait_s_per_gb", scan(s))
            + read("d2h_copy_s_per_gb", scan(s)) + (0.25 + 0.5) * s * 2)
    # the parent's one block of 5.5 s reads the same through the same file
    request = {"bytes": GB // 2, "ok": True, "device": PARENT}
    assert read("d2h_wait_s_per_gb", request) == pytest.approx(5.75 * 2)
    # and what no stage covers is what it was: the children are inside
    assert read("host_unattributed_s_per_gb",
                dict(scan(), read_cobol_s=80.0, to_arrow_s=0.0)) == (
        pytest.approx((80.0 - sum(scan()["device"]["stage_s"].values())) * 2))


@pytest.mark.jax
def test_a_real_device_read_feeds_every_new_reader(tmp_path):
    """A small multisegment read through the interpreted kernel on the
    CPU: the record of its scan gives every new reader something to
    read, and the sums hold on it."""
    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK, generate_exp3

    path = tmp_path / "exp3.bin"
    path.write_bytes(bytes(generate_exp3(600, seed=34)))
    data = read_cobol(
        str(path), backend="pallas", copybook_contents=EXP3_COPYBOOK,
        is_record_sequence="true", segment_field="SEGMENT-ID",
        redefine_segment_id_map="STATIC-DETAILS => C",
        redefine_segment_id_map_1="CONTACTS => P", parallelism="3",
        input_split_records="200")
    data.to_arrow()
    device = data.metrics.as_dict()["device"]
    request = {"bytes": path.stat().st_size, "ok": True, "device": device}
    values = {name: read(name, request) for name in NEW_METRICS}
    assert all(isinstance(v, float) and v >= 0.0 for v in values.values())
    stage_s = device["stage_s"]
    per_gb = GB / request["bytes"]
    assert read("d2h_wait_s_per_gb", request) == pytest.approx(
        (stage_s["launch"] + stage_s["d2h_wait"] + stage_s["d2h_wait.ready"]
         + stage_s["d2h_wait.copy"]) * per_gb)
    assert values["plan_index_s_per_gb"] == pytest.approx(
        (stage_s["plan_index"] + stage_s["plan_index.scan"]) * per_gb)
    # the link is busy no longer than its threads sat in the copy, and
    # the stage's share of the wall is no more than either
    busy_s = values["d2h_link_busy_s_per_gb"] / per_gb
    assert busy_s <= device["d2h_copy_thread_s"] + 1e-9
    assert stage_s["d2h_wait.copy"] <= busy_s + 1e-6
    assert values["d2h_copy_gb_per_s"] == pytest.approx(
        device["d2h_bytes"] / device["d2h_copy_thread_s"] / GB)
    assert 0.0 <= values["d2h_strided_share"] <= 1.0


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_manifest_declares_the_metric(metric):
    spec = manifest.load()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == metric]
    unit, better, source, layer, cells = NEW_METRICS[metric]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == (unit, better, source, layer)
    assert entry["moves"] == "scan_mb_per_s"
    assert entry.get("workloads") == cells
    # appended behind what the benchmark had: PR 33's last metric first
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index(metric) > names.index("assemble_list_slots_s_per_gb")
    # a rate or a share of 13.5 fetched bytes a million says nothing
    if cells is not None:
        assert "tpch_q6_q1" not in cells
