"""The device programs of the main path compile for a TPU v5e.

The TPU's compiler is installed with JAX and compiles for a chip that is
described, not attached: these tests hand it the real programs at the
widths chip_smoke.py runs them at, with the Pallas kernel forced out of
interpret mode, and check what interpret-mode parity tests cannot — that
Mosaic accepts the kernels (tiling, fast-memory use), that the program
fits the device, and which collectives a mesh puts in. Nothing runs:
a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, so a test file that
loaded it while being collected would take it from every other xdist
worker. For the same reason everything compiles in this process, in
this one file.
"""
import os
import re

import numpy as np
import pytest

from cobrix_tpu import parse_copybook
from cobrix_tpu.copybook.datatypes import Encoding
from cobrix_tpu.ops import pallas_tpu
from cobrix_tpu.reader.columnar import ColumnarDecoder, _pallas_group_spec
from cobrix_tpu.testing.generators import (EXP1_COPYBOOK, EXP2_COPYBOOK,
                                           EXP3_COPYBOOK)

pytestmark = pytest.mark.jax

KERNEL = "tpu_custom_call"
GATHER = " gather("
# the kernel's input among its custom call's operand layouts, by
# orientation (ops/pallas_tpu.py): `[B, bytes]` rows in the sublanes and a
# group's columns in the lanes, or `[bytes, B / 128, 128]` rows in the lanes
ROW_TILES = re.compile(r"operand_layout_constraints=\{u8\[\d+,\d+\]")
ROWS_IN_LANES = re.compile(
    r"operand_layout_constraints=\{s32\[\d+\]\{0\}, u8\[\d+,\d+,128\]")
EXP3_EXTENT = 16064
HBM_BYTES = 16 * 1024 ** 3  # one v5e chip
# temporaries of PR 26's programs (the parent of the change that took the
# string groups off XLA's gathers), compiled here the same way
PARENT_EXP2_TEMP_BYTES = 2_650_646_528
PARENT_EXP3_TEMP_BYTES = 400_620_544


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Force the fused kernel out of interpret mode for code that picks
    the mode from jax.default_backend() (the CPU, here)."""
    build = pallas_tpu.build_fused_decode

    def forced(groups, record_len, interpret=None):
        return build(groups, record_len, interpret=False)

    monkeypatch.setattr(pallas_tpu, "build_fused_decode", forced)


def exp3_copybook():
    return parse_copybook(EXP3_COPYBOOK,
                          segment_redefines=["STATIC_DETAILS", "CONTACTS"])


def kernel_calls(text: str):
    """(row-tile kernels, rows-in-lanes kernels) in a compiled program."""
    calls = [line for line in text.splitlines()
             if KERNEL in line and " custom-call(" in line]
    tiles = sum(bool(ROW_TILES.search(line)) for line in calls)
    lanes = sum(bool(ROWS_IN_LANES.search(line)) for line in calls)
    assert tiles + lanes == len(calls), calls
    return tiles, lanes


def compile_on(sharding, fn, batch: int, extent: int):
    import jax

    x = jax.ShapeDtypeStruct((batch, extent), np.uint8, sharding=sharding)
    compiled = jax.jit(fn).lower(x).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
    return compiled


def full_block(decoder) -> int:
    """The batch a big read launches (ColumnarDecoder._device_block)."""
    return decoder._device_block(10 ** 9, decoder.plan.max_extent)


@pytest.mark.parametrize("batch", [2048, "full_block"])
def test_exp3_decode_pallas(one_chip, mosaic, batch):
    decoder = ColumnarDecoder(exp3_copybook(), backend="pallas")
    assert decoder.plan.max_extent == EXP3_EXTENT
    fn = decoder.build_jax_decode_fn()
    assert fn.interpret is False
    if batch == "full_block":
        batch = full_block(decoder)
        assert batch == 8192
    compiled = compile_on(one_chip, fn, batch, EXP3_EXTENT)
    assert KERNEL in compiled.as_text()
    # the eight string groups are static slices of one looked-up plane
    assert GATHER not in compiled.as_text()
    # both OCCURS 2000 groups fill the lanes: the row-tile kernel
    assert fn.device_groups == {"fused": 2, "fused_rows_in_lanes": 0,
                                "sliced": 8, "gathered": 0}
    assert kernel_calls(compiled.as_text()) == (1, 0)
    if batch == 8192:
        # the kernel's planes are all but 1 MB of it, as in the parent
        # (the string gathers' temporaries were small at 8,192 rows); the
        # code points of the covered 64 bytes are the 64 KB more
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < PARENT_EXP3_TEMP_BYTES + 2 * batch * 64


@pytest.mark.parametrize("redefine, batch, extent, groups", [
    ("STATIC_DETAILS", 8192, EXP3_EXTENT,
     {"fused": 2, "fused_rows_in_lanes": 0, "sliced": 6, "gathered": 0}),
    ("CONTACTS", 16384, 60,
     {"fused": 0, "fused_rows_in_lanes": 0, "sliced": 4, "gathered": 0}),
])
def test_exp3_set_programs(one_chip, mosaic, redefine, batch, extent,
                           groups):
    """The two programs an exp3 read launches by redefine, at the shapes
    a 100 MiB shard gives them (about 6.5 k 'C' rows and 13 k 'P' rows):
    the 'C' rows' keeps the kernel and reads 16,064 B rows, the 'P'
    rows' is four string groups over 60 B rows and holds no kernel."""
    decoder = ColumnarDecoder(exp3_copybook(), backend="pallas")
    n = 19_500
    company = np.arange(n) % 3 == 0
    sets = {rs.name: rs for rs in decoder._segment_sets(
        {"STATIC_DETAILS": company, "CONTACTS": ~company}, n)}
    rs = sets[redefine]
    assert rs.extent == extent
    assert decoder._device_block(len(rs.rows), rs.extent) == batch
    fn = decoder.build_jax_decode_fn(groups=rs.groups)
    assert fn.device_groups == groups
    assert fn.interpret is (False if groups["fused"] else None)
    compiled = compile_on(one_chip, fn, batch, extent)
    assert kernel_calls(compiled.as_text()) == (
        int(bool(groups["fused"])), 0)
    assert GATHER not in compiled.as_text()
    if redefine == "STATIC_DETAILS":
        # the whole program's kernel planes, and no more
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < PARENT_EXP3_TEMP_BYTES + 2 * batch * 64


def test_exp2_decode_pallas_full_block(one_chip, mosaic):
    """64 B records of strings, half a vreg's lanes, at the largest batch
    the decoder launches (2,097,152 rows when this was written: a 100 MiB
    shard of an exp2 read is 1.6 million records). No gather is left (the
    parent's program held 456), and fewer temporaries than the parent's.
    The one COMP column is decoded with the rows in the lanes: 512 grid
    steps where the row-tile kernel took 65,536."""
    decoder = ColumnarDecoder(
        parse_copybook(EXP2_COPYBOOK,
                       segment_redefines=["STATIC_DETAILS", "CONTACTS"]),
        backend="pallas")
    assert decoder.plan.max_extent == 64
    batch = full_block(decoder)
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups == {"fused": 1, "fused_rows_in_lanes": 1,
                                "sliced": 8, "gathered": 0}
    # the strings leave as one matrix of 8-bit code points, the union
    # of both redefines' bytes: 64 + 5 B a row where eight uint16 slabs
    # and the COMP column took 223
    assert (fn.points.width, fn.points.dtype) == (64, np.uint8)
    compiled = compile_on(one_chip, fn, batch, 64)
    assert kernel_calls(compiled.as_text()) == (0, 1)
    assert GATHER not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < PARENT_EXP2_TEMP_BYTES
    assert mem.output_size_in_bytes < batch * 223
    print(f"exp2 {batch}x64: {mem.argument_size_in_bytes} B in, "
          f"{mem.output_size_in_bytes} B out, "
          f"{mem.temp_size_in_bytes} B of temporaries")


def test_exp3_decode_xla_gather(one_chip):
    decoder = ColumnarDecoder(exp3_copybook(), backend="jax")
    fn = decoder.build_jax_decode_fn()
    assert fn.interpret is None
    compiled = compile_on(one_chip, fn, 2048, EXP3_EXTENT)
    assert KERNEL not in compiled.as_text()
    # the route keeps its old name; its groups are strided slices now
    assert fn.device_groups == {"fused": 0, "fused_rows_in_lanes": 0,
                                "sliced": 10, "gathered": 0}
    assert GATHER not in compiled.as_text()


@pytest.mark.parametrize("n_chips", [1, 4])
@pytest.mark.parametrize("columns", ["occurs_2000", "single_columns"])
def test_device_aggregator(topo, mosaic, n_chips, columns):
    """NUM1+NUM2 over the exp3 'C' records (two groups that fill the
    lanes: the row-tile kernel), and three single columns of the kinds
    copybook (the rows-in-lanes kernel): the kernel inside `shard_map` on
    every mesh, and the cross-chip reduction only on the four-chip one."""
    import jax
    from jax.sharding import Mesh

    from cobrix_tpu.parallel import DeviceAggregator

    mesh = Mesh(np.asarray(topo.devices[:n_chips]), axis_names=("data",))
    if columns == "occurs_2000":
        agg = DeviceAggregator(exp3_copybook(), columns=["NUM1", "NUM2"],
                               active_segment="STATIC_DETAILS", mesh=mesh,
                               backend="pallas")
    else:
        agg = DeviceAggregator(parse_copybook(KINDS_COPYBOOK),
                               columns=["BIN-I32", "BCD-I64", "DSP-I32"],
                               mesh=mesh, backend="pallas")
    program = agg.device_program()
    assert program.interpreted is False
    assert program.device_groups["fused_rows_in_lanes"] == (
        0 if columns == "occurs_2000" else 3)
    compiled, built = program.compiled_for(
        jax.ShapeDtypeStruct((2048, agg.record_extent), np.uint8),
        jax.ShapeDtypeStruct((), np.int32))
    assert built and compiled.has_kernel
    text = compiled.executable.as_text()
    assert kernel_calls(text) == (
        (1, 0) if columns == "occurs_2000" else (0, 1))
    assert ("all-reduce" in text) == (n_chips > 1)


@pytest.mark.parametrize("name", ["q6", "q1"])
def test_tpch_query_programs(one_chip, mosaic, name):
    """The cell tpch_q6_q1's two programs at the shape a 64 MiB read chunk
    of 149 B records launches (450,395 rows in a bucket of 458,752): the
    kernel, the predicate and the grouped integer reductions in one
    program, no gather, scatter or sort, far inside the chip's memory, a
    few hundred bytes out."""
    import json

    import jax

    from benchmark.generators import tpch_lineitem
    from cobrix_tpu.parallel.query import DeviceAggregator, bind_query
    from cobrix_tpu.query.expr import parse_filter
    from cobrix_tpu.reader.arrow_out import arrow_schema
    from cobrix_tpu.reader.schema import CobolOutputSchema
    from cobrix_tpu.copybook.datatypes import SchemaRetentionPolicy
    from cobrix_tpu.stats.aggregate import parse_specs

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "tpch_lineitem_sf1.json")) as f:
        q = json.load(f)["queries"][name]
    copybook = parse_copybook(tpch_lineitem.COPYBOOK)
    schema = arrow_schema(CobolOutputSchema(
        copybook, policy=SchemaRetentionPolicy.COLLAPSE_ROOT).schema)
    agg = DeviceAggregator(copybook, backend="pallas", query=bind_query(
        copybook, parse_specs(q["aggs"]), parse_filter(q["filter"]),
        q.get("group_by", []), schema))
    extent = {"q6": 29, "q1": 38}[name]
    assert agg.decoder.plan.max_extent == extent
    program = agg.device_program()
    assert program.interpreted is False
    rows = agg.decoder._bucket_size(450_395)
    compiled = program._jit.lower(
        jax.ShapeDtypeStruct((rows, extent), np.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < HBM_BYTES // 8
    assert mem.output_size_in_bytes < 32768
    text = compiled.as_text()
    # two narrow groups a query (COMP-3 and the DISPLAY date): one
    # rows-in-lanes kernel of 112 grid steps over a bucket of 458,752
    # rows, where 32 rows a step made 14,336
    assert rows == 458_752
    assert program.device_groups["fused"] == 2
    assert program.device_groups["fused_rows_in_lanes"] == 2
    assert kernel_calls(text) == (0, 1) and GATHER not in text
    assert " scatter(" not in text and " sort(" not in text


# one group of every fused kind x output width (ops/pallas_tpu.py
# StridedGroup); the display kind follows the copybook's encoding
KINDS_COPYBOOK = """
       01 R.
          05 BIN-I32   PIC S9(6)  COMP.
          05 BIN-I64   PIC S9(12) COMP.
          05 BIN-WIDE  PIC S9(25) COMP.
          05 BCD-I32   PIC S9(7)  COMP-3.
          05 BCD-I64   PIC S9(15) COMP-3.
          05 BCD-WIDE  PIC S9(25) COMP-3.
          05 DSP-I32   PIC S9(5).
          05 DSP-I64   PIC S9(15).
          05 DSP-WIDE  PIC S9(25).
"""


@pytest.mark.parametrize("orientation", ["row_tiles", "rows_in_lanes"])
@pytest.mark.parametrize("batch", [256, 4096])
def test_kinds_matrix(one_chip, monkeypatch, batch, orientation):
    """Every fused kind and output width through both orientations of the
    kernel (these single columns take the rows in the lanes; an OCCURS of
    128 or more of any of them takes the row tiles)."""
    if orientation == "row_tiles":
        monkeypatch.setattr(pallas_tpu, "LANE_FILL_MIN", 1)
    covered = set()
    for encoding in (Encoding.EBCDIC, Encoding.ASCII):
        decoder = ColumnarDecoder(
            parse_copybook(KINDS_COPYBOOK, data_encoding=encoding),
            backend="pallas")
        groups = [_pallas_group_spec(g) for g in decoder.kernel_groups]
        assert all(groups)
        covered.update((g.kind, g.out) for g in groups)
        fused = pallas_tpu.build_fused_decode(
            groups, decoder.plan.max_extent, interpret=False)
        assert fused.interpret is False
        lanes = orientation == "rows_in_lanes"
        assert fused.rows_in_lanes == (len(groups) if lanes else 0)
        compiled = compile_on(one_chip, fused, batch,
                              decoder.plan.max_extent)
        assert kernel_calls(compiled.as_text()) == (
            (0, 1) if lanes else (1, 0))
    assert covered == {
        (kind, out)
        for kind in ("binary", "bcd", "display_ebcdic", "display_ascii")
        for out in ("i32", "i64", "wide")}


def test_program_of_both_orientations(one_chip):
    """An OCCURS 2000 group between a single column and three irregular
    ones: one row-tile kernel and one rows-in-lanes kernel in one
    program, at the batch an exp3 read launches."""
    groups = [
        pallas_tpu.StridedGroup([3], 5, "bcd"),
        pallas_tpu.StridedGroup([40 + 6 * k for k in range(2000)], 4,
                                "binary", signed=True),
        pallas_tpu.StridedGroup([10, 21, 29], 8, "binary", out="i64")]
    fused = pallas_tpu.build_fused_decode(groups, 12040, interpret=False)
    assert fused.rows_in_lanes == 2
    compiled = compile_on(one_chip, fused, 8192, 12040)
    assert kernel_calls(compiled.as_text()) == (1, 1)


def test_rows_in_lanes_at_the_vector_memory_budget(one_chip):
    """The most a row that one rows-in-lanes call may read and write
    (pallas_tpu.LANE_ROW_BYTES_MAX, double-buffered at LANE_TILE rows a
    step) fits the chip's vector memory; one column more is a second
    call. 38-digit DISPLAY: 38 bytes in, eleven 4-byte planes out."""
    cost = 38 + 4 * 11
    count = pallas_tpu.LANE_ROW_BYTES_MAX // cost
    assert count < pallas_tpu.LANE_FILL_MIN

    def group(first, columns):
        return pallas_tpu.StridedGroup(
            [first + 40 * k for k in range(columns)], 38, "display_ebcdic",
            out="wide", signed=True)

    fused = pallas_tpu.build_fused_decode(
        [group(0, count)], 40 * count, interpret=False)
    compiled = compile_on(one_chip, fused, 65536, 40 * count)
    assert kernel_calls(compiled.as_text()) == (0, 1)
    fused = pallas_tpu.build_fused_decode(
        [group(0, count), group(40 * count, 1)], 40 * count + 40,
        interpret=False)
    compiled = compile_on(one_chip, fused, 65536, 40 * count + 40)
    assert kernel_calls(compiled.as_text()) == (0, 2)


def test_exp1_decode_pallas_whole_program(one_chip, mosaic):
    """Every kernel kind at irregular offsets, strings and floats beside
    them, at the batch a big exp1 read launches. The slow one (about
    three quarters of a minute): exp1's kernel holds 61 groups, each one
    loop over its columns. No gather is left anywhere in the program: no
    group on the XLA route keeps one by the slice limit (two string
    groups and two float groups, one or two adjacent columns each), and
    the kernel's 61 narrow groups (15 columns at most) read their bytes
    from the transposed record matrix (`cobrix.planes`), where a field's
    bytes are adjacent leading indices whatever its offset."""
    decoder = ColumnarDecoder(parse_copybook(EXP1_COPYBOOK),
                              backend="pallas")
    batch = full_block(decoder)
    assert batch == 65536
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups == {"fused": 61, "fused_rows_in_lanes": 61,
                                "sliced": 4, "gathered": 0}
    compiled = compile_on(one_chip, fn, batch, decoder.plan.max_extent)
    text = compiled.as_text()
    assert kernel_calls(text) == (0, 1)
    assert GATHER not in text


def _exp1_decoder():
    return ColumnarDecoder(parse_copybook(EXP1_COPYBOOK), backend="pallas")


def _exp2_decoder():
    return ColumnarDecoder(
        parse_copybook(EXP2_COPYBOOK,
                       segment_redefines=["STATIC_DETAILS", "CONTACTS"]),
        backend="pallas")


def _hier_decoder():
    from benchmark.generators import hier_companies

    return ColumnarDecoder(
        parse_copybook(hier_companies.COPYBOOK,
                       segment_redefines=list(hier_companies.SEGMENTS)),
        backend="pallas")


@pytest.mark.parametrize("cell, build, records, batch, extent, out_row", [
    # a 64 MiB chunk of 44,949 records, 65,536 rows at the power of two;
    # 2,240 B a row of outputs in the chip's layouts, 1,862 fetched
    ("exp1", _exp1_decoder, 44_949, 49_152, 1493, 2304),
    # a 20 MiB shard of about 313 k records, 524,288 at the power of two
    ("hier", _hier_decoder, 313_000, 327_680, 108, 192),
    ("exp2", _exp2_decoder, 316_000, 327_680, 64, 128),
])
def test_programs_at_a_quarter_octave_bucket(one_chip, mosaic, cell, build,
                                             records, batch, extent,
                                             out_row):
    """A cell's whole program at the bucket its launch now pads to, a
    quarter octave above its records where the power of two was up to
    an octave: the one rows-in-lanes kernel call it makes at a power of
    two, on grid steps that need no padding, no gather, and inside the
    chip's memory."""
    decoder = build()
    assert decoder.plan.max_extent == extent
    assert decoder._device_block(records, extent) == batch
    assert batch % pallas_tpu.LANE_TILE == 0
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups["gathered"] == 0
    compiled = compile_on(one_chip, fn, batch, extent)
    text = compiled.as_text()
    assert kernel_calls(text) == (0, 1)
    assert GATHER not in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes < batch * out_row
    print(f"{cell} {batch}x{extent}: {mem.argument_size_in_bytes} B in, "
          f"{mem.output_size_in_bytes} B out, "
          f"{mem.temp_size_in_bytes} B of temporaries")


def test_tpch_orders_program_with_the_expansion(one_chip, mosaic):
    """The cell tpch_orders_odo_read's program at the batch a big read
    launches (a quarter of the other programs' bytes a launch,
    columnar.EXPAND_BLOCK_SHARE: 16,384 rows): the expansion of the
    1,153 B rows (three static shifts of the bytes behind the array and
    a select each), then the one static
    program: numeric groups of 3 to 28 columns with the rows in the
    lanes, the strings sliced. No byte is moved by a gather: the only
    one is the count's own power-of-ten lookup, a value a row."""
    from benchmark.generators import tpch_orders_nested

    decoder = ColumnarDecoder(parse_copybook(tpch_orders_nested.COPYBOOK),
                              backend="pallas", variable_size_occurs=True)
    assert len(decoder.regions) == 1
    assert decoder.plan.max_extent == tpch_orders_nested.MAX_RECORD == 1153
    batch = full_block(decoder)
    assert batch == 16384
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups == {"fused": 4, "fused_rows_in_lanes": 4,
                                "sliced": 6, "gathered": 0}
    widest = max(len(g.columns) for g in decoder.kernel_groups
                 if _pallas_group_spec(g) is not None)
    assert 16 <= widest < pallas_tpu.LANE_FILL_MIN
    # every string byte of the expanded row, once, 8 bits a code point
    assert (fn.points.width, fn.points.dtype) == (677, np.uint8)
    compiled = compile_on(one_chip, fn, batch, 1153)
    text = compiled.as_text()
    assert kernel_calls(text) == (0, 1)
    assert not [line for line in text.splitlines()
                if GATHER in line and " u8[" in line.split(GATHER)[0]]
    mem = compiled.memory_analysis()
    print(f"orders {batch}x1153: {mem.argument_size_in_bytes} B in, "
          f"{mem.output_size_in_bytes} B out, "
          f"{mem.temp_size_in_bytes} B of temporaries")


def test_hier_decode_pallas_full_block(one_chip, mosaic):
    """upstream's hierarchical copybook (seven segment redefines of one
    107 B area) as one decode-once program at the largest batch the
    decoder launches: 1,048,576 rows of 108 B, the block cap, two to a
    100 MiB shard of 1.56 million records. Six fused groups of one or two
    columns, the narrowest the rows-in-lanes kernel is given, in one
    kernel call; sixteen sliced string groups whose code points leave as
    one 8-bit matrix of the 107 B behind the id byte; no gather."""
    from benchmark.generators import hier_companies

    decoder = ColumnarDecoder(
        parse_copybook(hier_companies.COPYBOOK,
                       segment_redefines=list(hier_companies.SEGMENTS)),
        backend="pallas")
    assert decoder.plan.max_extent == 108
    batch = full_block(decoder)
    assert batch == 1_048_576
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups == {"fused": 6, "fused_rows_in_lanes": 6,
                                "sliced": 16, "gathered": 0}
    assert (fn.points.width, fn.points.dtype) == (107, np.uint8)
    compiled = compile_on(one_chip, fn, batch, 108)
    assert kernel_calls(compiled.as_text()) == (0, 1)
    assert GATHER not in compiled.as_text()
    mem = compiled.memory_analysis()
    # 157 B a launched row come home: the matrix and the narrow planes
    assert mem.output_size_in_bytes < batch * 192
    print(f"hier {batch}x108: {mem.argument_size_in_bytes} B in, "
          f"{mem.output_size_in_bytes} B out, "
          f"{mem.temp_size_in_bytes} B of temporaries")


def test_tpch_customers_programs_of_the_two_row_kinds(one_chip, mosaic):
    """The cell tpch_customers_odo_read's two programs at the batches a
    big read launches: the element rows' (an order less O-CUSTKEY, 1,149 B,
    its lines a region: the expansion, then the orders' kind of static
    program) at a quarter of the other programs' bytes a launch, 16,384
    rows, and the owner rows' (the customer's columns and the comment
    behind the orders, 224 B, no region) at its block cap. No byte is
    moved by a gather."""
    from benchmark.generators import tpch_customers_nested

    copybook = parse_copybook(tpch_customers_nested.COPYBOOK)
    shapes = {"element": (16384, 1149), "owner": (524288, 224)}
    for kind, (rows, extent) in shapes.items():
        decoder = ColumnarDecoder(copybook, backend="pallas",
                                  variable_size_occurs=True,
                                  rows_of=(kind, "C_ORDERS"))
        assert len(decoder.regions) == (kind == "element")
        assert decoder.plan.max_extent == extent
        assert full_block(decoder) == rows
        fn = decoder.build_jax_decode_fn()
        assert fn.device_groups["gathered"] == 0
        compiled = compile_on(one_chip, fn, rows, extent)
        text = compiled.as_text()
        assert kernel_calls(text) == (0, 1)
        assert not [line for line in text.splitlines()
                    if GATHER in line and " u8[" in line.split(GATHER)[0]]
        mem = compiled.memory_analysis()
        print(f"customers {kind} {rows}x{extent}: "
              f"{mem.argument_size_in_bytes} B in, "
              f"{mem.output_size_in_bytes} B out, "
              f"{mem.temp_size_in_bytes} B of temporaries")
