"""`preframed_shard_share` (PR 36): the share of an indexed scan's shards
that got their records' tables from the index pass. The reader on recorded
requests whose answers are known (1.0, 0.0, a mix, nothing to read), on a
real read of each route on the CPU, and as the manifest declares it."""
import pytest

from benchmark_testing import REPO  # noqa: F401

from benchmark import manifest
from benchmark.harness import load_named

GB = 10 ** 9
RDW = ["exp3_read", "exp2_read", "tpch_orders_odo_read"]


def scan(ok: bool = True, **device) -> dict:
    return {"bytes": GB // 2, "ok": ok,
            "device": dict({"stage_s": {"pack": 1.0}}, **device)}


def read(*window):
    record = {"warm": {"requests": []}, "window": {"requests": list(window)}}
    return load_named("layer_metrics", "preframed_shard_share").read(record)


DENSE = scan(preframed_shards=6, self_framed_shards=0)
SPARSE = scan(preframed_shards=0, self_framed_shards=6)


@pytest.mark.parametrize("window,want", [
    ((DENSE, DENSE, DENSE), 1.0),
    ((SPARSE, SPARSE), 0.0),
    # a read of a dense and a sparse file; a scan that failed says nothing
    ((scan(preframed_shards=6, self_framed_shards=2),
      scan(False, preframed_shards=0, self_framed_shards=100)), 0.75),
    ((DENSE, SPARSE, SPARSE), 1 / 3),
], ids=["dense", "sparse", "mixed_read", "mixed_window"])
def test_reader_gives_the_known_share(window, want):
    value = read(*window)
    assert isinstance(value, float)
    assert value == pytest.approx(want)


@pytest.mark.parametrize("device", [
    None,                                                  # a host read
    {"launches": {"65536x1493": 8}, "stage_s": {"pack": 1.0}},  # no shards
    {"stage_s": {"plan_index": 1.6, "frame": 0.6}},        # the parent
], ids=["no_device_record", "a_read_that_cuts_no_shards",
        "program_without_the_counts"])
def test_reader_finds_nothing_to_read(device):
    assert read({"bytes": GB, "ok": True, "device": device}) is None
    assert read() is None
    assert read(scan(False, preframed_shards=6)) is None


@pytest.mark.jax
@pytest.mark.parametrize("records,want", [(1300, 1.0), (40, 0.0)],
                         ids=["dense_exp2", "sparse_exp3"])
def test_a_real_device_read_of_each_route(tmp_path, records, want):
    """exp2's 64 B records take the index pass as their one framing;
    exp3's, 5.4 KB at the mean, frame shard by shard as ever."""
    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing import generators

    path = tmp_path / "file.bin"
    if want:
        path.write_bytes(generators.generate_exp2(records, seed=36))
        options = dict(copybook_contents=generators.EXP2_COPYBOOK,
                       segment_id_level0="C", segment_id_level1="P",
                       segment_id_prefix="A", input_split_records="300")
    else:
        path.write_bytes(bytes(generators.generate_exp3(records, seed=36)))
        options = dict(copybook_contents=generators.EXP3_COPYBOOK,
                       input_split_records="10")
    data = read_cobol(
        str(path), backend="pallas", is_record_sequence="true",
        segment_field="SEGMENT-ID",
        redefine_segment_id_map="STATIC-DETAILS => C",
        redefine_segment_id_map_1="CONTACTS => P", parallelism="3",
        **options)
    data.to_arrow()
    metrics = data.metrics.as_dict()
    device = metrics["device"]
    assert (device["preframed_shards"] + device["self_framed_shards"]
            == metrics["shards"] > 2)
    value = read({"bytes": path.stat().st_size, "ok": True,
                  "device": device})
    assert isinstance(value, float) and value == want


def test_the_manifest_declares_the_metric():
    spec = manifest.load()
    (entry,) = [m for m in spec["per_layer"]
                if m["name"] == "preframed_shard_share"]
    assert entry == {"name": "preframed_shard_share", "unit": "share",
                     "better": "higher", "source": "program_counter",
                     "layer": "executor", "moves": "scan_mb_per_s",
                     "workloads": RDW}
    # appended behind what the benchmark had: PR 34's last metric first
    assert spec["per_layer"][-2]["name"] == "plan_index_s_per_gb"
    assert spec["per_layer"][-1] is entry
    assert manifest.problems(spec) == []
