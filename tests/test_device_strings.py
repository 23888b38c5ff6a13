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
    return decoder.collect_outputs(jax.jit(fn)(arr), arr.shape[0],
                                   points=fn.points), fn


def points_dtype(lut):
    """The width of a code point on the link: a byte where the code
    page's table fits one."""
    return np.uint8 if int(lut.max()) <= 0xFF else np.uint16


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
    assert got.dtype == points_dtype(lut) and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # the one COMP column rides the kernel with the rows in the lanes
    assert fn.device_groups == {"fused": int(backend == "pallas"),
                                "fused_rows_in_lanes": int(
                                    backend == "pallas"),
                                "sliced": 2 - int(backend == "pallas"),
                                "gathered": 0}
    assert decoder.device_program().device_groups["points_u8"] == int(
        lut.max() <= 0xFF)


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
        dtype = (points_dtype(decoder.lut)
                 if c.codec is Codec.EBCDIC_STRING else want.dtype)
        assert got.dtype == dtype and got.shape == want.shape, c.name
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


@pytest.mark.parametrize("layout,backend", [
    ("overlapping_redefines", "jax"), ("overlapping_redefines", "pallas"),
    ("evenly_spaced_columns", "jax")])
def test_empty_batch(layout, backend):
    """No row at all, with the strings in the merged spans and with a
    group's own block behind them (beside a numeric group there, which
    the interpreted kernel does not take without a row: a read pads an
    empty batch to a bucket)."""
    text, options = LAYOUTS[layout]
    decoder = ColumnarDecoder(parse_copybook(text, **options),
                              backend=backend)
    arr = np.zeros((0, decoder.plan.max_extent), dtype=np.uint8)
    outputs, fn = run_program(decoder, arr)
    assert bool(fn.points.blocks) == (layout == "evenly_spaced_columns")
    for c in decoder.plan.columns:
        if c.codec is Codec.EBCDIC_STRING:
            assert outputs[c.index]["bytes"].shape == (0, c.width)
            assert outputs[c.index]["bytes"].dtype == np.uint8
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
                                   "sliced": 8, "gathered": 0,
                                   "points_u8": 1}),
    "exp3": (EXP3_COPYBOOK, True, {"fused": 2, "fused_rows_in_lanes": 0,
                                   "sliced": 8, "gathered": 0,
                                   "points_u8": 1}),
    "exp1": (EXP1_COPYBOOK, False,
             {"fused": 61, "fused_rows_in_lanes": 61, "sliced": 4,
              "gathered": 0, "points_u8": 1}),
}


@pytest.mark.parametrize("name", sorted(UPSTREAM))
def test_device_groups_of_upstream_copybooks(name):
    text, multiseg, want = UPSTREAM[name]
    cb = parse_copybook(text, segment_redefines=(
        ["STATIC_DETAILS", "CONTACTS"] if multiseg else []))
    decoder = ColumnarDecoder(cb, backend="pallas")
    # the routes are the decode's; whether its matrix of code points
    # is 8-bit is said of the read's program, which fetches it
    routes = {k: v for k, v in want.items() if k != "points_u8"}
    assert decoder.build_jax_decode_fn().device_groups == routes
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
                "gathered": 0, "points_u8": 2}
    data = read_cobol(str(path), backend="pallas", **options)
    device = data.metrics.as_dict()["device"]
    assert device["device_groups"] == want
    assert data.metrics.as_dict()["device_groups"] == want
    assert device["interpreted"] is True


# ------------------------------------------- the matrix of code points
#
# An EBCDIC string leaves a device program once, in the lookup's own
# [rows, width] matrix, 8 bits a code point where the code page's table
# fits a byte; `to_arrow` builds every string column of a batch from it
# in one native pass. Held to the scalar oracle (`backend="host"`).

LATIN_COPYBOOK = """
       01 R.
          05 NAME  PIC X(8).
          05 N     PIC S9(4) COMP.
          05 NOTE  PIC X(6).
          05 E  OCCURS 3.
             10 TAG  PIC X(4).
             10 Q    PIC 9(3) COMP-3.
"""


def encode_page(page: str, text: str, width: int) -> bytes:
    """`text` in the code page's own bytes, padded with its space."""
    lut = cached_code_page_lut(page)
    byte_of = {chr(int(point)): b for b, point in enumerate(lut)}
    return bytes(byte_of[ch] for ch in text.ljust(width))


def latin_records(page: str, words) -> np.ndarray:
    """One 34 B record a row of `words`: (NAME, NOTE, three TAGs)."""
    rows = []
    for i, (name, note, tags) in enumerate(words):
        row = encode_page(page, name, 8) + (i * 7).to_bytes(2, "big") \
            + encode_page(page, note, 6)
        for k, tag in enumerate(tags):
            row += encode_page(page, tag, 4) + bytes([0x10 + k, 0x2F])
        rows.append(np.frombuffer(row, dtype=np.uint8))
    return np.stack(rows)


LATIN_WORDS = [
    ("café", " 5¢", ("Zür", "é", "plus")),
    ("Zürich", "plain", ("abcd", " üü ", "")),
    ("", "¢¢¢¢¢¢", ("¢", "x", "é é")),
    ("  padded", "é", ("ok", "ok", "ok")),
]
GREEK_WORDS = [
    ("ΩΩ", "αβγ", ("Ω", "ab", "")),
    ("plain", " Ω ", ("αΩ", "Ω", "abcd")),
]
TRIMS = ("none", "left", "right", "both")


def read_pair(path, backend, **options):
    from cobrix_tpu import read_cobol

    device = read_cobol(str(path), backend=backend, **options)
    oracle = read_cobol(str(path), backend="host", **options)
    return device, oracle


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("page,words,eight_bit", [
    ("cp037", LATIN_WORDS, True), ("cp500", LATIN_WORDS, True),
    ("cp875", GREEK_WORDS, False)])
def test_code_points_past_ascii(page, words, eight_bit, backend, tmp_path):
    """Latin-1 code points (é, ü, ¢: 0x80-0xFF, one byte each on the
    link) and cp875's Greek (16 bits still) through to_arrow(), through
    rows, through DecodedBatch.value and inside an OCCURS of structs."""
    arr = latin_records(page, words)
    path = tmp_path / "latin.bin"
    path.write_bytes(arr.tobytes())
    options = dict(copybook_contents=LATIN_COPYBOOK, ebcdic_code_page=page)
    device, oracle = read_pair(path, backend, **options)
    table = device.to_arrow()
    assert table.equals(oracle.to_arrow(), check_metadata=True)
    record = table.column("R").combine_chunks()
    assert record.field("NAME").to_pylist() == [w[0].strip() for w in words]
    assert [[e["TAG"] for e in row] for row in
            record.field("E").to_pylist()] == [
        [t.strip() for t in w[2]] for w in words]
    assert device.to_rows() == oracle.to_rows()
    metrics = device.metrics.as_dict()
    assert metrics["device_groups"]["points_u8"] == int(eight_bit)
    # NAME and NOTE in one pass, the TAG slots (past 0x7F: the flat
    # route declines them) in a second; 16-bit points take neither
    assert metrics.get("native_passes", {}).get("point_strings", 0) == (
        2 if eight_bit else 0)

    decoder = ColumnarDecoder(parse_copybook(LATIN_COPYBOOK,
                                             ebcdic_code_page=page),
                              backend=backend)
    batch = decoder.decode(arr)
    want = ColumnarDecoder(decoder.copybook, backend="numpy").decode(arr)
    for c in decoder.plan.columns:
        if c.codec is Codec.EBCDIC_STRING:
            out = batch.column_arrays(c.index)
            assert out["bytes"].dtype == points_dtype(decoder.lut)
            assert out["bytes"].base is not None  # a view, no copy
        for i in range(len(words)):
            assert batch.value(c.index, i) == want.value(c.index, i)


EXP2_OPTIONS = dict(
    copybook_contents=EXP2_COPYBOOK, is_record_sequence="true",
    segment_field="SEGMENT-ID",
    redefine_segment_id_map="STATIC-DETAILS => C",
    redefine_segment_id_map_1="CONTACTS => P")


def exp2_file(tmp_path, records: int = 300, tail: bytes = b""):
    from cobrix_tpu.testing.generators import generate_exp2

    path = tmp_path / "exp2.bin"
    path.write_bytes(generate_exp2(records, seed=12) + tail)
    return path


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("trim", TRIMS)
def test_exp2_both_redefines_every_trim(trim, backend, tmp_path):
    """exp2's copybook: the rows a redefine hides are null, whatever the
    trim, and every string column of the batch comes of one native pass
    over the one matrix."""
    path = exp2_file(tmp_path)
    device, oracle = read_pair(path, backend, string_trimming_policy=trim,
                               **EXP2_OPTIONS)
    table = device.to_arrow()
    assert table.equals(oracle.to_arrow(), check_metadata=True)
    record = table.column("COMPANY_DETAILS").combine_chunks()
    company, contact = (record.field(name) for name in (
        "STATIC_DETAILS", "CONTACTS"))
    ids = record.field("SEGMENT_ID").to_pylist()
    assert company.is_valid().to_pylist() == [
        s.strip() == "C" for s in ids]
    assert contact.is_valid().to_pylist() == [
        s.strip() == "P" for s in ids]
    metrics = device.metrics.as_dict()
    assert metrics["native_passes"]["point_strings"] == 1
    assert "string_transcode" not in metrics["native_passes"]
    assert metrics["device"]["declined_batches"] == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_exp2_truncated_tail_keeps_the_scalar_path(backend, tmp_path):
    """A last 'P' record cut inside CONTACT-PERSON: that column goes
    the scalar-owned way (partial field rules), the others the pass."""
    from cobrix_tpu.testing.generators import _rdw, ebcdic_encode

    short = (ebcdic_encode("P", 5) + ebcdic_encode("1234567890", 10)
             + ebcdic_encode("+(123) 456 78 90", 17)
             + ebcdic_encode("Jane Roe", 8))
    path = exp2_file(tmp_path, 200, _rdw(len(short)) + short)
    device, oracle = read_pair(path, backend, **EXP2_OPTIONS)
    table = device.to_arrow()
    assert table.equals(oracle.to_arrow(), check_metadata=True)
    last = table.column("COMPANY_DETAILS").combine_chunks()[-1].as_py()
    assert last["CONTACTS"] == {"PHONE_NUMBER": "+(123) 456 78 90",
                                "CONTACT_PERSON": "Jane Roe"}
    assert device.metrics.as_dict()["native_passes"]["point_strings"] == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_exp2_strings_cross_the_link_once(backend, tmp_path):
    """The program's string output is ONE [rows, 64] uint8 array (the
    union of both redefines' bytes), and a launched row brings 69 B
    home: 64 code points, TAXPAYER-NUM's int32 and its bool (223 with a
    uint16 slab a kernel group)."""
    import jax

    from cobrix_tpu import read_cobol

    cb = parse_copybook(EXP2_COPYBOOK,
                        segment_redefines=["STATIC_DETAILS", "CONTACTS"])
    decoder = ColumnarDecoder(cb, backend=backend)
    fn = decoder.build_jax_decode_fn()
    arr = np.random.default_rng(2).integers(
        0x40, 0xFA, size=(256, 64), dtype=np.uint8)
    outs = jax.jit(fn)(arr)
    strings = [gi for gi, g in enumerate(decoder.kernel_groups)
               if g.codec is Codec.EBCDIC_STRING]
    assert len(strings) == 8 and all(outs[gi] == () for gi in strings)
    assert len(outs) == len(decoder.kernel_groups) + 1
    # (the same row-major bytes, 128 lanes a row on the link)
    (points,) = outs[-1]
    assert points.dtype == np.uint8 and points.shape == (128, 128)
    assert fn.points.width == 64 and fn.points.spans == [(0, 64)]
    np.testing.assert_array_equal(
        np.asarray(points).reshape(256, 64),
        batch_np.transcode_ebcdic(arr, decoder.lut))
    # a program round decode_all reads a group's planes as before
    for gi in strings:
        g = decoder.kernel_groups[gi]
        planes = fn.group_planes(outs, gi)
        assert planes.values.shape == (256, len(g.columns), g.width)
        np.testing.assert_array_equal(
            planes.values[:, 0],
            decoder.lut[arr[:, g.offsets[0]:g.offsets[0] + g.width]])

    path = exp2_file(tmp_path)
    stats = read_cobol(str(path), backend=backend,
                       **EXP2_OPTIONS).metrics.as_dict()["device"]
    assert list(stats["launches"]) == ["512x64"]
    assert stats["h2d_bytes"] == 512 * 64
    assert stats["d2h_bytes"] == 512 * 69


@pytest.mark.parametrize("backend", BACKENDS + ("numpy",))
def test_the_counters_that_say_it_engaged(backend, tmp_path):
    from cobrix_tpu import read_cobol

    path = exp2_file(tmp_path)
    data = read_cobol(str(path), backend=backend, **EXP2_OPTIONS)
    data.to_arrow()
    metrics = data.metrics.as_dict()
    passes = metrics.get("native_passes", {})
    if backend == "numpy":
        assert "device_groups" not in metrics
        assert passes.get("point_strings", 0) == 0
        assert passes["string_transcode"] == 1
    else:
        assert metrics["device_groups"]["points_u8"] == 1
        assert metrics["device"]["device_groups"]["points_u8"] == 1
        assert passes["point_strings"] == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_without_the_native_library(backend, tmp_path, monkeypatch):
    """The same table where the one pass is not to be had."""
    from cobrix_tpu import native, read_cobol

    arr = latin_records("cp037", LATIN_WORDS)
    latin = tmp_path / "latin.bin"
    latin.write_bytes(arr.tobytes())
    files = [(exp2_file(tmp_path), EXP2_OPTIONS),
             (latin, dict(copybook_contents=LATIN_COPYBOOK,
                          ebcdic_code_page="cp037"))]
    want = [read_cobol(str(p), backend=backend, **o).to_arrow()
            for p, o in files]
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", True)
    assert not native.available()
    for (p, o), table in zip(files, want):
        data = read_cobol(str(p), backend=backend, **o)
        assert data.to_arrow().equals(table, check_metadata=True)
        assert "point_strings" not in data.metrics.as_dict().get(
            "native_passes", {})
