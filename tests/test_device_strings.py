"""Groups on the XLA route of the device decode: bytes by static slices,
EBCDIC code points by the compare-and-select lookup.

Everything here runs on the CPU (`backend="jax"`, and `backend="pallas"`
with its kernel interpreted) and holds the device program's outputs to
the host kernels bit for bit: `batch_np.transcode_ebcdic` for every code
page, the numpy backend for every column of every layout the slicing
rule tells apart.
"""
import numpy as np
import pytest

from cobrix_tpu import parse_copybook
from cobrix_tpu.copybook.datatypes import DebugFieldsPolicy, Encoding
from cobrix_tpu.encoding import codepages
from cobrix_tpu.ops import batch_np
from cobrix_tpu.plan.cache import cached_code_page_lut
from cobrix_tpu.plan.compiler import Codec
from cobrix_tpu.reader import columnar
from cobrix_tpu.reader.columnar import (ColumnarDecoder, _merged_spans,
                                        _slice_pieces)
from cobrix_tpu.testing.generators import (EXP1_COPYBOOK, EXP2_COPYBOOK,
                                           EXP3_COPYBOOK)

pytestmark = pytest.mark.jax

BACKENDS = ("jax", "pallas")
# every page the repo ships, and one registered at run time whose table
# is a shuffle (about 256 runs: hardly two neighbours share a value or an
# offset) of code points up to 0xC838
SCRAMBLED = "test_scrambled_page"
CODE_PAGES = sorted(codepages._TABLES) + [SCRAMBLED]


@pytest.fixture(scope="module", autouse=True)
def scrambled_page():
    order = np.random.default_rng(5).permutation(256)
    table = "".join(chr(0x100 + 200 * int(k)) for k in order)
    codepages.register_code_page(SCRAMBLED, table)


def run_program(decoder, arr):
    """The device program's own outputs for `arr`, as collect_outputs
    hands them to the host."""
    import jax

    fn = decoder.build_jax_decode_fn()
    return decoder.collect_outputs(jax.jit(fn)(arr), arr.shape[0]), fn


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("page", CODE_PAGES)
def test_all_bytes_of_every_code_page(page, backend):
    """All 256 byte values, in a field wider than a vreg's 128 lanes,
    beside a numeric the Pallas kernel takes and zero rows as a padded
    launch has them."""
    cb = parse_copybook("""
       01 R.
          05 S  PIC X(256).
          05 N  PIC S9(4) COMP.
""", ebcdic_code_page=page)
    lut = cached_code_page_lut(page)
    assert lut.dtype == np.uint16 and lut.shape == (256,)
    rng = np.random.default_rng(3)
    arr = np.zeros((6, 258), dtype=np.uint8)
    arr[0, :256] = np.arange(256)
    arr[1, :256] = np.arange(255, -1, -1)
    arr[2:4] = rng.integers(0, 256, size=(2, 258))
    decoder = ColumnarDecoder(cb, backend=backend)
    outputs, fn = run_program(decoder, arr)
    (s_col,) = [c for c in decoder.plan.columns if c.name == "S"]
    got = outputs[s_col.index]["bytes"]
    want = batch_np.transcode_ebcdic(arr[:, :256], lut)
    assert got.dtype == np.uint16 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # the one COMP column rides the kernel with the rows in the lanes
    assert fn.device_groups == {"fused": int(backend == "pallas"),
                                "fused_rows_in_lanes": int(
                                    backend == "pallas"),
                                "sliced": 2 - int(backend == "pallas"),
                                "gathered": 0}


def irregular_copybook(columns: int) -> str:
    """`columns` X(3) fields with 1 to 4 other bytes between neighbours,
    in no progression."""
    lines = ["       01 R."]
    for i in range(columns):
        lines.append(f"          05 S{i} PIC X(3).")
        lines.append(f"          05 G{i} PIC 9({1 + (i * i + i // 3) % 4}).")
    return "\n".join(lines)


LAYOUTS = {
    # name: (copybook, parse options)
    "one_column": ("""
       01 R.
          05 A  PIC 9(3).
          05 S  PIC X(12).
""", {}),
    "adjacent_columns": ("""
       01 R.
          05 S  PIC X(6) OCCURS 5.
""", {}),
    "evenly_spaced_columns": ("""
       01 R.
          05 E  OCCURS 6.
             10 S  PIC X(5).
             10 N  PIC S9(4) COMP.
""", {}),
    "irregular_columns": (irregular_copybook(7), {}),
    "irregular_columns_past_the_limit": (
        irregular_copybook(columnar.SLICE_PIECES_MAX + 9), {}),
    "overlapping_redefines": ("""
       01 R.
          05 SEG  PIC X(1).
          05 A.
             10 A1  PIC X(15).
             10 A2  PIC X(25).
             10 A3  PIC X(8).
          05 B REDEFINES A.
             10 B1  PIC X(17).
             10 B2  PIC X(28).
             10 B3  PIC X(3).
""", {"segment_redefines": ["A", "B"]}),
    "width_1": ("""
       01 R.
          05 S  PIC X(1) OCCURS 9.
          05 T  PIC X(1).
""", {}),
    "width_over_128": ("""
       01 R.
          05 S  PIC X(300).
          05 T  PIC X(129) OCCURS 2.
""", {}),
    "ascii_strings": ("""
       01 R.
          05 S  PIC X(7).
          05 N  PIC 9(3).
          05 T  PIC X(7).
""", {"data_encoding": Encoding.ASCII}),
    "raw_strings": ("""
       01 R.
          05 S  PIC X(7).
          05 N  PIC S9(4) COMP.
""", {"debug_fields_policy": DebugFieldsPolicy.RAW}),
    "float_group": ("""
       01 R.
          05 F  COMP-1.
          05 S  PIC X(3).
          05 D  COMP-2.
          05 G  COMP-1.
""", {}),
}


def string_reference(decoder, spec, arr):
    """What the host kernels make of one string column's bytes."""
    slab = arr[:, spec.offset:spec.offset + spec.width]
    if spec.codec is Codec.EBCDIC_STRING:
        return batch_np.transcode_ebcdic(slab, decoder.lut)
    if spec.codec is Codec.ASCII_STRING:
        return batch_np.mask_ascii(slab)
    return slab


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_layout_parity(layout, backend):
    text, options = LAYOUTS[layout]
    cb = parse_copybook(text, **options)
    decoder = ColumnarDecoder(cb, backend=backend)
    host = ColumnarDecoder(cb, backend="numpy")
    rng = np.random.default_rng(len(layout))
    extent = decoder.plan.max_extent
    arr = rng.integers(0, 256, size=(13, extent), dtype=np.uint8)
    arr[-2:] = 0  # rows as a padded launch has them
    outputs, fn = run_program(decoder, arr)
    strings = [c for c in decoder.plan.columns
               if c.codec in columnar._STRING_CODECS]
    assert strings
    for c in strings:
        got = outputs[c.index]["bytes"]
        want = string_reference(decoder, c, arr)
        assert got.dtype == want.dtype and got.shape == want.shape, c.name
        np.testing.assert_array_equal(got, want, err_msg=c.name)
    # every column, through the decoder's own entry point
    out_dev, out_host = decoder.decode(arr), host.decode(arr)
    for c in decoder.plan.columns:
        for i in range(arr.shape[0]):
            a, b = out_dev.value(c.index, i), out_host.value(c.index, i)
            assert a == b or (a != a and b != b), f"{c.name} record {i}"
    routes = fn.device_groups
    assert routes["gathered"] == int(
        layout == "irregular_columns_past_the_limit"), routes
    if layout == "float_group" and backend == "jax":
        # the two floats are one group, evenly spaced; the double another
        assert routes == {"fused": 0, "fused_rows_in_lanes": 0,
                          "sliced": 3, "gathered": 0}


@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_batch(backend):
    cb = parse_copybook(LAYOUTS["overlapping_redefines"][0],
                        segment_redefines=["A", "B"])
    decoder = ColumnarDecoder(cb, backend=backend)
    arr = np.zeros((0, decoder.plan.max_extent), dtype=np.uint8)
    outputs, _ = run_program(decoder, arr)
    for c in decoder.plan.columns:
        assert outputs[c.index]["bytes"].shape == (0, c.width)
        assert outputs[c.index]["bytes"].dtype == np.uint16
    assert decoder.decode(arr).n_records == 0


def test_slice_pieces():
    assert _slice_pieces([7], 5) == [(7, 1, 5)]
    assert _slice_pieces([0, 6, 12, 18], 6) == [(0, 4, 6)]
    assert _slice_pieces([3, 10, 17], 5) == [(3, 3, 7)]
    # a second run starts where the step changes; order is the group's
    assert _slice_pieces([0, 10, 30, 50], 4) == [(0, 2, 10), (30, 2, 20)]
    assert _slice_pieces([40, 0, 4], 4) == [(40, 1, 4), (0, 2, 4)]
    # columns that overlap (redefines of one width) are never one run
    assert _slice_pieces([15, 17], 8) == [(15, 1, 8), (17, 1, 8)]


def test_merged_spans():
    assert _merged_spans([(30, 55), (0, 5), (5, 15), (15, 32), (56, 64)]) \
        == [(0, 55), (56, 64)]
    assert _merged_spans([]) == []


UPSTREAM = {
    # the three copybooks of upstream's performance suite on the
    # benchmark's backend. exp2: TAXPAYER-NUM is the one numeric group;
    # exp3 adds the OCCURS planes (COMP and COMP-3 are one fused group
    # each, TAXPAYER-NUM rides with the COMP one); exp1: 61 numeric
    # groups, three string columns in two groups (two of them adjacent)
    # and two float groups, none past the slice limit. Of the fused
    # groups only exp3's two fill the 128 lanes with columns (OCCURS
    # 2000); every other puts the batch's rows there
    "exp2": (EXP2_COPYBOOK, True, {"fused": 1, "fused_rows_in_lanes": 1,
                                   "sliced": 8, "gathered": 0}),
    "exp3": (EXP3_COPYBOOK, True, {"fused": 2, "fused_rows_in_lanes": 0,
                                   "sliced": 8, "gathered": 0}),
    "exp1": (EXP1_COPYBOOK, False,
             {"fused": 61, "fused_rows_in_lanes": 61, "sliced": 4,
              "gathered": 0}),
}


@pytest.mark.parametrize("name", sorted(UPSTREAM))
def test_device_groups_of_upstream_copybooks(name):
    text, multiseg, want = UPSTREAM[name]
    cb = parse_copybook(text, segment_redefines=(
        ["STATIC_DETAILS", "CONTACTS"] if multiseg else []))
    decoder = ColumnarDecoder(cb, backend="pallas")
    assert decoder.build_jax_decode_fn().device_groups == want
    assert decoder.device_program().device_groups == want
    # without the kernel its groups are sliced like the others
    on_xla = ColumnarDecoder(cb, backend="jax").build_jax_decode_fn()
    assert on_xla.device_groups == {
        "fused": 0, "fused_rows_in_lanes": 0,
        "sliced": want["fused"] + want["sliced"], "gathered": 0}


def test_read_metrics_carry_device_groups(tmp_path):
    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing.generators import generate_exp2

    path = tmp_path / "exp2.bin"
    path.write_bytes(generate_exp2(300, seed=4))
    options = dict(copybook_contents=EXP2_COPYBOOK,
                   is_record_sequence="true", segment_field="SEGMENT-ID",
                   **{"redefine_segment_id_map:1": "STATIC-DETAILS => C",
                      "redefine-segment-id-map:2": "CONTACTS => P"})
    data = read_cobol(str(path), backend="pallas", **options)
    metrics = data.metrics.as_dict()
    assert metrics["device_groups"] == UPSTREAM["exp2"][2]
    assert metrics["device"]["device_groups"] == UPSTREAM["exp2"][2]
    assert data.metrics.device_stats.device_groups == UPSTREAM["exp2"][2]
    # a host read launches nothing and says nothing of routes
    host = read_cobol(str(path), backend="numpy", **options)
    assert "device_groups" not in host.metrics.as_dict()


@pytest.mark.parametrize("shape", ["exp1", "exp3"])
def test_read_metrics_say_which_way_the_kernel_was_turned(tmp_path, shape):
    """`fused_rows_in_lanes` through a read's own record: every fused
    group of a fixed-length file of scattered narrow numerics (exp1), none
    of a file whose numerics are two OCCURS 2000 planes (exp3, the two
    programs of its launches by redefine summed)."""
    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing.generators import generate_exp1, generate_exp3

    path = tmp_path / f"{shape}.bin"
    if shape == "exp1":
        path.write_bytes(generate_exp1(20, seed=6).tobytes())
        options = dict(copybook_contents=EXP1_COPYBOOK)
        want = UPSTREAM["exp1"][2]
    else:
        path.write_bytes(generate_exp3(40, seed=6))
        options = dict(copybook_contents=EXP3_COPYBOOK,
                       is_record_sequence="true",
                       segment_field="SEGMENT-ID",
                       redefine_segment_id_map="STATIC-DETAILS => C",
                       redefine_segment_id_map_1="CONTACTS => P")
        want = {"fused": 2, "fused_rows_in_lanes": 0, "sliced": 10,
                "gathered": 0}
    data = read_cobol(str(path), backend="pallas", **options)
    device = data.metrics.as_dict()["device"]
    assert device["device_groups"] == want
    assert data.metrics.as_dict()["device_groups"] == want
    assert device["interpreted"] is True
