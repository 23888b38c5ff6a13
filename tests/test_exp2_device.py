"""Upstream exp2 (RDW multisegment narrow, Seg_Id generation on) through
the device backends, on the CPU at a small size: both device decoders
against the scalar oracle and the host kernels across index shard cuts,
the Seg_Id state machine across those cuts, and the stages and the record
count the read leaves behind."""
import pytest

from cobrix_tpu import read_cobol
from cobrix_tpu.reader.var_len_reader import (SegmentIdAccumulator,
                                              _segment_level_ids_vectorized)
from cobrix_tpu.testing.generators import EXP2_COPYBOOK, generate_exp2

pytestmark = pytest.mark.jax

RECORDS = 1300
OPTIONS = dict(copybook_contents=EXP2_COPYBOOK, is_record_sequence="true",
               segment_field="SEGMENT-ID",
               redefine_segment_id_map="STATIC-DETAILS => C",
               redefine_segment_id_map_1="CONTACTS => P",
               segment_id_level0="C", segment_id_level1="P",
               segment_id_prefix="A", input_split_records="300")


@pytest.fixture(scope="module", params=[False, True],
                ids=["rdw_little_endian", "rdw_big_endian"])
def exp2_file(request, tmp_path_factory):
    """(path, reader options, the scalar oracle's table) of one exp2 file
    in one RDW byte order."""
    big_endian = request.param
    path = tmp_path_factory.mktemp("exp2") / "companies.dat"
    path.write_bytes(generate_exp2(RECORDS, seed=2147483999,
                                   big_endian_rdw=big_endian))
    options = dict(OPTIONS, is_rdw_big_endian=str(big_endian).lower())
    oracle = read_cobol(str(path), backend="host", **options).to_arrow()
    assert oracle.num_rows == RECORDS
    return str(path), options, oracle


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_device_backend_equals_oracle_and_host_kernels_across_shards(
        exp2_file, backend):
    path, options, oracle = exp2_file
    data = read_cobol(path, backend=backend, **options)
    table = data.to_arrow()
    assert data.metrics.shards >= 3
    assert table.equals(oracle)
    host = read_cobol(path, backend="numpy", **options)
    assert host.metrics.shards >= 3
    assert table.equals(host.to_arrow(), check_metadata=True)
    # Seg_Id1 restarts at every root, whatever shard the root fell into
    seg_id0 = table.column("Seg_Id0").to_pylist()
    seg_id1 = table.column("Seg_Id1").to_pylist()
    assert seg_id0[0] == "A_0_0" and seg_id1[0] is None
    assert all(b is None or b.startswith(a + "_L1_")
               for a, b in zip(seg_id0, seg_id1))


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_the_read_counts_its_seg_id_stages_and_its_records(exp2_file,
                                                           backend):
    path, options, oracle = exp2_file
    data = read_cobol(path, backend=backend, **options)
    before = data.metrics.as_dict()["device"]
    assert before["stage_n"]["seg_id"] == data.metrics.shards
    assert "assemble.seg_id" not in before["stage_s"]
    table = data.to_arrow()
    device = data.metrics.as_dict()["device"]
    assert device["stage_n"]["assemble.seg_id"] == data.metrics.shards
    assert device["stage_s"]["seg_id"] > 0
    assert device["stage_s"]["assemble.seg_id"] > 0
    assert device["records"] == table.num_rows == RECORDS
    launched = sum(int(shape.split("x")[0]) * n
                   for shape, n in device["launches"].items())
    # padded rows go over the link whole; what comes back is the program's
    assert device["h2d_bytes"] == launched * 64 > RECORDS * 64
    assert device["d2h_bytes"] > 0


def test_a_read_without_seg_id_generation_counts_no_seg_id_stage(exp2_file):
    path, options, _ = exp2_file
    plain = {k: v for k, v in options.items()
             if not k.startswith("segment_id_")}
    data = read_cobol(path, backend="jax", **plain)
    data.to_arrow()
    stage_s = data.metrics.as_dict()["device"]["stage_s"]
    assert "seg_id" not in stage_s and "assemble.seg_id" not in stage_s


def test_the_scalar_oracle_is_left_unstaged(exp2_file):
    """The oracle's per-record SegmentIdAccumulator walk is the code the
    parent had: a stage entered per record would cost more than the two
    calls it times."""
    path, options, _ = exp2_file
    data = read_cobol(path, backend="host", **options)
    assert "seg_id" not in data.metrics.device_stats.stage_n


def segment_ids_of(raw: bytes, big_endian: bool) -> list:
    ids, pos = [], 0
    while pos < len(raw):
        length = (raw[pos] << 8 | raw[pos + 1]) if big_endian \
            else (raw[pos + 2] | raw[pos + 3] << 8)
        ids.append(raw[pos + 4:pos + 5].decode("cp037"))
        pos += 4 + length
    return ids


@pytest.mark.parametrize("cuts", [(), (1,), (3, 4, 400)],
                         ids=["whole", "after_first_root", "three_cuts"])
def test_vectorized_seg_ids_equal_the_accumulator_across_shard_cuts(cuts):
    """The per-record SegmentIdAccumulator over the whole file against
    `_segment_level_ids_vectorized` shard by shard, the shards cut at
    roots as the sparse index cuts them."""
    ids = segment_ids_of(generate_exp2(RECORDS, seed=7), False)
    levels = ["C", "P"]
    accumulator = SegmentIdAccumulator(levels, "A", 3)
    want = []
    for index, segment_id in enumerate(ids):
        accumulator.acquired_segment_id(segment_id, index)
        want.append([accumulator.get_segment_level_id(k)
                     for k in range(len(levels))])
    roots = [i for i, s in enumerate(ids) if s == "C"]
    starts = [0] + [roots[c] for c in cuts] + [len(ids)]
    got = []
    for start, end in zip(starts, starts[1:]):
        columns, no_root = _segment_level_ids_vectorized(
            ids[start:end], levels, "A", 3, start)
        assert not no_root.any()
        got += [columns[i] for i in range(end - start)]
    assert got == want
