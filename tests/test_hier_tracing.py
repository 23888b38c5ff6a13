"""A hierarchical (`segment-children`) read says what it did: the stage
`assemble.hier` once a shard and `.assign` / `.leaves` once a struct of
the tree, the `hier_*` counts on every batched backend, a counted record walk with its reason where the
columnar assembly declines; and the device backends' tables equal the
scalar oracle's tree for tree across forced shard cuts."""
import numpy as np
import pytest

from benchmark.generators import hier_companies as hier
from cobrix_tpu import native, profiling, read_cobol
from cobrix_tpu.explain import explain

pytestmark = pytest.mark.jax

SEED = 2147483777
OPTIONS = dict(
    copybook_contents=hier.COPYBOOK, is_record_sequence="true",
    segment_field="SEGMENT-ID", generate_record_id="true",
    **{f"redefine_segment_id_map:{i}": f"{name} => {i + 1}"
       for i, name in enumerate(hier.SEGMENTS)},
    **{f"segment-children:{i}": f"{parent} => {child}"
       for i, (child, parent) in enumerate(hier.PARENT.items())})
# entries a shard: the assembly once; ENTITY, COMPANY and the six child
# segments build their leaves once each; positions by segment, COMPANY's
# row mask under ENTITY and the six child assignments
HIER_STAGES = {"assemble.hier": 1, "assemble.hier.assign": 8,
               "assemble.hier.leaves": 8}
# a shard's string leaves are built at their rows in one pass a struct
# that has any (not ENTITY): COMPANY and the six child segments
STRING_PASSES = 7


def written(tmp_path, companies: int):
    data, facts = hier.generate(companies, SEED)
    path = tmp_path / "hier.dat"
    path.write_bytes(data)
    return str(path), facts


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return written(tmp_path_factory.mktemp("hier_small"), 600)


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_counts_equal_the_generators_and_stages_fire_by_struct_not_record(
        small, backend, monkeypatch):
    path, facts = small
    records = int(facts["segment_records"].sum())
    # the stages open round each call of the string leaves' kernel
    subset_kernel, opened = native.string_cols_arrow_raw, []

    def noted(*args, **kwargs):
        opened.append(tuple(frame.name for frame in profiling._open.stack))
        return subset_kernel(*args, **kwargs)

    monkeypatch.setattr(native, "string_cols_arrow_raw", noted)
    data = read_cobol(path, backend=backend,
                      input_split_records=str(records // 4), **OPTIONS)
    table = data.to_arrow()
    metrics = data.metrics.as_dict()
    shards = metrics["shards"]
    assert shards >= 4 and table.num_rows == 600
    # 18 string and 5 integer leaves a shard built at their rows, the
    # AMOUNT decimal at full length and taken
    assert metrics["hier"] == {
        "hier_roots": 600, "hier_records": records,
        "hier_children": records - 600, "hier_orphans": 0,
        "hier_leaves_at": 23 * shards, "hier_leaves_taken": shards,
        "hier_row_path_roots": 0}
    stats = data.metrics.device_stats
    assert {stage: stats.stage_n[stage] for stage in HIER_STAGES} == \
        {stage: entries * shards for stage, entries in HIER_STAGES.items()}
    # the string leaves' pass, beneath the leaves' stage, once a struct
    # of the tree and never a record; no full-length pass over the
    # fetched code points
    assert stats.stage_n["assemble.string"] == STRING_PASSES * shards
    assert opened == [("assemble.hier", "assemble.hier.leaves",
                       "assemble.string")] * (STRING_PASSES * shards)
    assert "point_strings" not in metrics.get("native_passes", {})
    if backend == "numpy":
        # its leaves are built at positions, from the file image
        assert "device" not in metrics
    else:
        device = metrics["device"]
        # a device batch's leaves are built at positions from the
        # fetched matrix of code points
        assert {k: v for k, v in device.items()
                if k.startswith("hier_")} == metrics["hier"]
        assert all(shape.endswith("x108") for shape in device["launches"])
        assert device["declined_batches"] == shards
        assert device["device_groups"]["fused"] == (
            6 if backend == "pallas" else 0)
        assert device["interpreted"] is (True if backend == "pallas"
                                         else None)
    assert hier.check_table(table, facts) == []


@pytest.mark.parametrize("backend, companies, split", [
    ("jax", 3800, {"input_split_size_mb": "1"}),
    ("pallas", 600, {"input_split_records": "2500"}),
])
def test_device_backends_equal_the_oracle_across_forced_cuts(
        tmp_path, backend, companies, split):
    path, facts = written(tmp_path, companies)
    data = read_cobol(path, backend=backend, **split, **OPTIONS)
    table = data.to_arrow()
    assert data.metrics.as_dict()["shards"] >= 4
    oracle = read_cobol(path, backend="host", **OPTIONS).to_arrow()
    assert table.equals(oracle)
    assert table.to_pylist() == oracle.to_pylist()
    # every cut was at a root: a shard's first Record_Id continues the
    # file's count, and the last is the file's record count
    ids = table.column("Record_Id").to_numpy()
    assert np.all(np.diff(ids) > 0)
    assert ids[-1] == facts["segment_records"].sum()
    assert hier.check_table(table, facts) == []


def test_a_select_read_is_walked_counted_and_says_why(small):
    path, _ = small
    data = read_cobol(path, backend="numpy", select=["COMPANY-NAME"],
                      **OPTIONS)
    data.to_arrow()
    counts = data.metrics.as_dict()["hier"]
    assert counts["hier_row_path_roots"] == 600 and counts["hier_roots"] == 0
    assert "select=" in counts["hier_decline_reason"]
    plan = explain(backend="numpy", select=["COMPANY-NAME"], **OPTIONS).plan
    assert plan["hierarchical"] == "rows"
    assert plan["hierarchical_reason"] == counts["hier_decline_reason"]
    assert explain(backend="numpy", **OPTIONS).plan["hierarchical"] == \
        "columnar"


def test_a_non_root_parent_under_two_ids_is_walked_counted_and_says_why(
        small):
    """DEPT, a parent that is no root, mapped from ids 2 and 8: the
    oracle scans past sibling occurrences with the other id, so the
    nesting is walked record by record over the batch's values."""
    path, _ = small
    options = dict(OPTIONS)
    options["redefine_segment_id_map:7"] = "DEPT => 8"
    data = read_cobol(path, backend="numpy", **options)
    table = data.to_arrow()
    counts = data.metrics.as_dict()["hier"]
    assert counts["hier_row_path_roots"] == 600 and counts["hier_roots"] == 0
    assert "DEPT is mapped from 2 segment ids" in \
        counts["hier_decline_reason"]
    plan = explain(backend="numpy", **options).plan
    assert plan["hierarchical"] == "batched_rows"
    assert plan["hierarchical_reason"] == counts["hier_decline_reason"]
    assert table.equals(read_cobol(path, backend="host",
                                   **options).to_arrow())

