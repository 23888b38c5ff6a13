"""The rows a device launch is padded to (`ColumnarDecoder._bucket_size`):
powers of two up to `BUCKET_OCTAVE_ROWS`, a quarter octave apart above
it, and what reads at those sizes give.

The rule is checked on its own (every bucket holds its rows, the buckets
are monotone, at least 80 % full, at most four an octave, multiples of
both kernels' grid steps) and at the counts the benchmark's cells
launch. Reads whose record counts fall on and one past a step of the
ladder then go through a device backend (on the CPU here: XLA's, and the
Pallas interpreter) and are held to the host kernels table for table,
with the rows launched and the records counted in the read's device
metrics."""
import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from cobrix_tpu import api, parse_copybook, query, read_cobol
from cobrix_tpu.parallel import query as device_query
from cobrix_tpu.reader import columnar
from cobrix_tpu.reader.columnar import (BUCKET_OCTAVE_ROWS, LAUNCH_ROW_STEP,
                                        ColumnarDecoder)
from cobrix_tpu.testing.generators import (EXP1_COPYBOOK, EXP2_COPYBOOK,
                                           encode_comp3_unsigned,
                                           encode_comp_be,
                                           encode_display_unsigned,
                                           encode_strings_column,
                                           generate_exp2)

pytestmark = pytest.mark.jax

BATCH_TILE = 32         # ops/pallas_tpu.BATCH_TILE: the row-tile step
LANE_TILE = 4096        # ops/pallas_tpu.LANE_TILE: the rows-in-lanes step


def bucket(n: int) -> int:
    return ColumnarDecoder._bucket_size(n)


def test_the_step_is_the_kernels_grid_step():
    from cobrix_tpu.ops import pallas_tpu

    assert LAUNCH_ROW_STEP == pallas_tpu.LANE_TILE == LANE_TILE
    assert pallas_tpu.BATCH_TILE == BATCH_TILE
    assert BUCKET_OCTAVE_ROWS == 4 * LANE_TILE


# the edges of the ladder, and sizes past any launch a read makes
EDGES = [0, 1, 255, 256, 257, 4095, 4096, 4097, 16383, 16384, 16385,
         20479, 20480, 20481, 24577, 32767, 32768, 32769, 44949, 65535,
         65536, 65537, 262144, 262145, 313000, 327680, 327681, 450395,
         2 ** 20 - 1, 2 ** 20, 2 ** 20 + 1, 10 ** 9, np.int64(44949)]


@pytest.mark.parametrize("n", EDGES, ids=str)
def test_a_bucket_holds_its_rows_and_neither_kernel_pads(n):
    b = bucket(n)
    assert isinstance(b, int) and b >= n and b >= 256
    if n <= BUCKET_OCTAVE_ROWS:
        # a power of two from 256, as before the ladder
        assert b & (b - 1) == 0
        assert b == 256 or b // 2 < n
    else:
        assert b % LANE_TILE == 0 and b % BATCH_TILE == 0
        assert 5 * n > 4 * b
    # the lane-dense matrix of code points divides into rows of 128
    assert (b * 64) % columnar.POINTS_LANES == 0


@pytest.mark.parametrize("octave", range(8, 25))
def test_buckets_are_monotone_and_at_most_four_an_octave(octave):
    """Every n of the octave (p, 2p], at a stride that meets every
    step's edge and both sides of it."""
    p = 2 ** octave
    step = max(1, p // 64)
    ns = sorted({n for k in range(p // step + 1)
                 for n in (p + k * step, p + k * step + 1)
                 if p < n <= 2 * p})
    buckets = [bucket(n) for n in ns]
    assert buckets == sorted(buckets)
    assert bucket(p) <= buckets[0] and buckets[-1] == 2 * p
    assert len(set(buckets)) == (1 if p < BUCKET_OCTAVE_ROWS else 4)
    if p >= BUCKET_OCTAVE_ROWS:
        assert sorted(set(buckets)) == [5 * p // 4, 3 * p // 2,
                                        7 * p // 4, 2 * p]


@pytest.mark.parametrize("n, padded", [
    (44949, 49152),     # exp1_read: a 64 MiB chunk of 1,493 B records
    (313000, 327680),   # hier_companies_read: a 20 MiB shard
    (330000, 393216),   # exp2_read: a shard past 327,680 records
    (450395, 458752),   # tpch_q6_q1: a 64 MiB chunk of 149 B records
    (11000, 16384),     # tpch_customers_odo_read's owner rows
    (8192, 8192),       # exp3_read's 'C' rows, under the threshold
])
def test_the_cells_counts_land_where_predicted(n, padded):
    assert bucket(n) == padded


@pytest.mark.parametrize("extent", [29, 38, 64, 108, 224, 1153, 1493,
                                    16064])
def test_a_block_never_exceeds_its_cap(extent):
    decoder = ColumnarDecoder(parse_copybook(EXP2_COPYBOOK), backend="jax")
    assert not decoder.regions
    cap = 256
    while cap * 2 * extent <= columnar.DEVICE_BLOCK_BYTES:
        cap *= 2
    for n in EDGES[1:]:
        block = decoder._device_block(n, extent)
        assert block <= cap
        assert block * extent <= max(columnar.DEVICE_BLOCK_BYTES,
                                     256 * extent)
        # a batch that fits one block goes as one launch of its bucket
        assert block == (bucket(n) if n <= cap else cap)


def test_a_program_with_regions_keeps_its_share_of_the_cap():
    from benchmark.generators import tpch_orders_nested

    decoder = ColumnarDecoder(parse_copybook(tpch_orders_nested.COPYBOOK),
                              backend="jax", variable_size_occurs=True)
    assert decoder.regions
    assert decoder._device_block(139_000, 1153) == 16384
    assert decoder._device_block(12_000, 1153) == 16384
    # exp1's chunk under no region: its bucket, a quarter octave up
    exp1 = ColumnarDecoder(parse_copybook(EXP1_COPYBOOK), backend="jax")
    assert exp1._device_block(44949, exp1.plan.max_extent) == 49152
    assert exp1._device_block(10 ** 9, exp1.plan.max_extent) == 65536


# ------------------------------------------------------------ reads

SMALL_COPYBOOK = """
       01 R.
          05 ID      PIC 9(8) COMP.
          05 NAME    PIC X(6).
          05 AMOUNT  PIC S9(7)V99 COMP-3.
          05 QTY     PIC 9(5).
"""
SMALL_RECORD = 4 + 6 + 5 + 5


def small_records(n: int, seed: int) -> bytes:
    rng = np.random.default_rng(seed)
    return np.concatenate([
        encode_comp_be(rng.integers(0, 10 ** 8, n), 4),
        encode_strings_column([f"n{i % 9973}" for i in range(n)], 6),
        encode_comp3_unsigned(rng.integers(0, 10 ** 9, n), 9),
        encode_display_unsigned(rng.integers(0, 10 ** 5, n), 5)],
        axis=1).tobytes()


def same_tables(path, backend, **options):
    """(the device read's metrics, its table), the table held to the host
    kernels' read of the same file."""
    device = read_cobol(str(path), backend=backend, **options)
    table = device.to_arrow()
    host = read_cobol(str(path), backend="numpy", **options).to_arrow()
    assert table.equals(host, check_metadata=True)
    return device.metrics.as_dict()["device"], table


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("n", [16384, 16385, 20480, 20481])
def test_fixed_length_reads_at_the_ladders_steps(tmp_path, backend, n):
    path = tmp_path / "small.dat"
    path.write_bytes(small_records(n, seed=2 ** 31 + n))
    stats, table = same_tables(path, backend,
                               copybook_contents=SMALL_COPYBOOK)
    assert table.num_rows == n
    extent = ColumnarDecoder(parse_copybook(SMALL_COPYBOOK)).plan.max_extent
    assert stats["launches"] == {f"{bucket(n)}x{extent}": 1}
    assert stats["records"] == n
    assert stats["launch_rows"] == bucket(n)
    assert stats["h2d_bytes"] == bucket(n) * extent


@pytest.fixture(scope="module")
def virtual_devices():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")


@pytest.mark.parametrize("n_devices", [1, 3, 8])
@pytest.mark.parametrize("n", [16385, 20481])
def test_a_sharded_decode_pads_to_the_bucket_rounded_to_its_mesh(
        virtual_devices, n_devices, n):
    from cobrix_tpu.parallel import ShardedColumnarDecoder, data_mesh

    copybook = parse_copybook(SMALL_COPYBOOK)
    decoder = ShardedColumnarDecoder(copybook,
                                     mesh=data_mesh(n_devices=n_devices))
    padded = decoder._mesh_bucket(n)
    assert padded % n_devices == 0
    assert bucket(n) <= padded < bucket(n) + n_devices
    data = np.frombuffer(small_records(n, seed=n), dtype=np.uint8).reshape(
        n, SMALL_RECORD)
    assert (decoder.decode(data).to_rows()
            == ColumnarDecoder(copybook, backend="numpy").decode(
                data).to_rows())


COMPANIES = dict(
    copybook_contents=EXP2_COPYBOOK, is_record_sequence="true",
    segment_field="SEGMENT-ID",
    redefine_segment_id_map="STATIC-DETAILS => C",
    redefine_segment_id_map_1="CONTACTS => P")


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("n", [20480, 20481])
def test_rdw_reads_at_the_ladders_steps(tmp_path, backend, n):
    """One RDW file one shard covers: the batch goes whole, as one launch
    of its bucket of 64 B rows."""
    path = tmp_path / "exp2.dat"
    path.write_bytes(generate_exp2(n, seed=2 ** 31 + 7))
    stats, table = same_tables(path, backend, **COMPANIES)
    assert table.num_rows == n
    assert stats["launches"] == {f"{bucket(n)}x64": 1}
    assert (stats["records"], stats["launch_rows"]) == (n, bucket(n))


@pytest.mark.parametrize("n", [16385, 20481])
def test_partitioned_rdw_reads_pad_each_set_to_its_bucket(tmp_path,
                                                          monkeypatch, n):
    """exp2's batch forced to launch by redefine: each set's rows go in a
    bucket of their own count, and the sets' rows and buckets add up."""
    monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 0)
    path = tmp_path / "exp2.dat"
    path.write_bytes(generate_exp2(n, seed=2 ** 31 + 11))
    stats, table = same_tables(path, "jax", **COMPANIES)
    assert stats["partitioned_batches"] == 1
    rows = stats["set_rows"]
    assert sum(rows.values()) == stats["records"] == n
    assert stats["launch_rows"] == sum(bucket(r) for r in rows.values())
    assert sum(stats["launches"].values()) == len(rows)


LINEITEM_Q6 = dict(
    aggs=["sum:L_EXTENDEDPRICE*L_DISCOUNT", "count"],
    filter="L_SHIPDATE >= 19940101 and L_SHIPDATE < 19950101 and "
           "L_DISCOUNT >= 0.05 and L_DISCOUNT <= 0.07 and L_QUANTITY < 24")


def test_the_query_aggregate_at_a_chunks_bucket(tmp_path, monkeypatch):
    """tpch_q6_q1's chunk of 450,395 records goes in a bucket of 458,752,
    seven quarters of 262,144. At a sixteenth of the size, under a block
    of 32,768 rows: a chunk of 28,150 records is one launch of 28,672,
    and one of 53,249 a whole block and a launch of 24,576."""
    from benchmark.generators import tpch_lineitem

    record = tpch_lineitem.RECORD_SIZE
    monkeypatch.setattr(device_query, "LAUNCH_ROWS_MAX", 2 ** 15)
    monkeypatch.setattr(api, "FIXED_READ_CHUNK_BYTES", 53_249 * record)
    data, _facts = tpch_lineitem.generate(53_249 + 28_150, 2 ** 31 + 5)
    path = tmp_path / "lineitem.dat"
    path.write_bytes(data)
    options = dict(copybook_contents=tpch_lineitem.COPYBOOK,
                   schema_retention_policy="collapse_root")

    def ask(backend):
        dataset = query.dataset(str(path), backend=backend, **options)
        return (dataset.aggregate(LINEITEM_Q6["aggs"],
                                  filter=LINEITEM_Q6["filter"]),
                dataset.metrics)

    got, metrics = ask("jax")
    want, _ = ask("numpy")
    assert got == want and str(got) == str(want)
    stats = metrics.as_dict()["device"]
    extent = next(iter(stats["launches"])).split("x")[1]
    assert stats["launches"] == {f"{rows}x{extent}": 1
                                 for rows in (24576, 28672, 32768)}
    assert stats["query_rows_scanned"] == stats["records"] == 81_399
    assert stats["launch_rows"] == 24576 + 28672 + 32768
