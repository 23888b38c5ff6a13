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
    assert fn.device_groups == {"fused": 2, "sliced": 8, "gathered": 0}
    if batch == 8192:
        # the kernel's planes are all but 1 MB of it, as in the parent
        # (the string gathers' temporaries were small at 8,192 rows); the
        # code points of the covered 64 bytes are the 64 KB more
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < PARENT_EXP3_TEMP_BYTES + 2 * batch * 64


@pytest.mark.parametrize("redefine, batch, extent, groups", [
    ("STATIC_DETAILS", 8192, EXP3_EXTENT,
     {"fused": 2, "sliced": 6, "gathered": 0}),
    ("CONTACTS", 16384, 60, {"fused": 0, "sliced": 4, "gathered": 0}),
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
    assert (KERNEL in compiled.as_text()) == bool(groups["fused"])
    assert GATHER not in compiled.as_text()
    if redefine == "STATIC_DETAILS":
        # the whole program's kernel planes, and no more
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < PARENT_EXP3_TEMP_BYTES + 2 * batch * 64


def test_exp2_decode_pallas_full_block(one_chip, mosaic):
    """64 B records of strings, half a vreg's lanes, at the largest batch
    the decoder launches (2,097,152 rows when this was written: a 100 MiB
    shard of an exp2 read is 1.6 million records). No gather is left (the
    parent's program held 456), and fewer temporaries than the parent's."""
    decoder = ColumnarDecoder(
        parse_copybook(EXP2_COPYBOOK,
                       segment_redefines=["STATIC_DETAILS", "CONTACTS"]),
        backend="pallas")
    assert decoder.plan.max_extent == 64
    batch = full_block(decoder)
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups == {"fused": 1, "sliced": 8, "gathered": 0}
    compiled = compile_on(one_chip, fn, batch, 64)
    assert KERNEL in compiled.as_text()
    assert GATHER not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < PARENT_EXP2_TEMP_BYTES
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
    assert fn.device_groups == {"fused": 0, "sliced": 10, "gathered": 0}
    assert GATHER not in compiled.as_text()


@pytest.mark.parametrize("n_chips", [1, 4])
def test_device_aggregator(topo, mosaic, n_chips):
    """NUM1+NUM2 over the exp3 'C' records: the kernel on every mesh, and
    the cross-chip reduction only on the four-chip one."""
    import jax
    from jax.sharding import Mesh

    from cobrix_tpu.parallel import DeviceAggregator

    mesh = Mesh(np.asarray(topo.devices[:n_chips]), axis_names=("data",))
    agg = DeviceAggregator(exp3_copybook(), columns=["NUM1", "NUM2"],
                           active_segment="STATIC_DETAILS", mesh=mesh,
                           backend="pallas")
    program = agg.device_program()
    assert program.interpreted is False
    compiled, built = program.compiled_for(
        jax.ShapeDtypeStruct((2048, agg.record_extent), np.uint8),
        jax.ShapeDtypeStruct((), np.int32))
    assert built and compiled.has_kernel
    text = compiled.executable.as_text()
    assert ("all-reduce" in text) == (n_chips > 1)


@pytest.mark.parametrize("name", ["q6", "q1"])
def test_tpch_query_programs(one_chip, mosaic, name):
    """The cell tpch_q6_q1's two programs at the shape a 64 MiB read chunk
    of 149 B records launches: the kernel, the predicate and the grouped
    integer reductions in one program, no gather, scatter or sort, far
    inside the chip's memory, a few hundred bytes out."""
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
    assert KERNEL in text and GATHER not in text
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


@pytest.mark.parametrize("batch", [256, 4096])
def test_kinds_matrix(one_chip, batch):
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
        compiled = compile_on(one_chip, fused, batch,
                              decoder.plan.max_extent)
        assert KERNEL in compiled.as_text()
    assert covered == {
        (kind, out)
        for kind in ("binary", "bcd", "display_ebcdic", "display_ascii")
        for out in ("i32", "i64", "wide")}


def test_exp1_decode_pallas_whole_program(one_chip, mosaic):
    """Every kernel kind at irregular offsets, strings and floats beside
    them, at the batch a big exp1 read launches. The slow one (about a
    minute and a half): exp1's kernel unrolls 59 groups. No group on the
    XLA route keeps a gather by the slice limit (two string groups and
    two float groups, one or two adjacent columns each); the gathers that
    are left feed the kernel its irregular numerics (`cobrix.planes`,
    pallas_tpu._byte_planes) and carry no group's scope."""
    decoder = ColumnarDecoder(parse_copybook(EXP1_COPYBOOK),
                              backend="pallas")
    batch = full_block(decoder)
    assert batch == 65536
    fn = decoder.build_jax_decode_fn()
    assert fn.device_groups == {"fused": 61, "sliced": 4, "gathered": 0}
    compiled = compile_on(one_chip, fn, batch, decoder.plan.max_extent)
    text = compiled.as_text()
    assert KERNEL in text
    for line in text.splitlines():
        if GATHER in line:
            assert "cobrix.group." not in line, line
            assert "cobrix.lookup." not in line, line

