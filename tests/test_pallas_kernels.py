"""Fused Pallas decode kernel parity vs the numpy blueprint kernels.

Runs in Pallas interpret mode on CPU (conftest pins JAX to the virtual CPU
mesh). tests/test_tpu_compile.py compiles the same kernels with Mosaic for
a described v5e; chip_smoke.py runs them on the chip.

The kernel has two orientations, chosen by a group's column count against
the 128 lanes (pallas_tpu.LANE_FILL_MIN): the parity tests run every case
through both, by moving that constant under the same small groups.
"""
import numpy as np
import pytest

from cobrix_tpu import parse_copybook
from cobrix_tpu.ops import batch_np, pallas_tpu
from cobrix_tpu.reader.columnar import ColumnarDecoder, _pallas_group_spec
from cobrix_tpu.testing.generators import (EXP1_COPYBOOK, EXP3_COPYBOOK,
                                           generate_exp1, generate_exp3)

pytestmark = pytest.mark.jax

ORIENTATIONS = ["row_tiles", "rows_in_lanes"]


@pytest.fixture(params=ORIENTATIONS)
def orientation(request, monkeypatch):
    """Every group of the test through one orientation of the kernel: a
    group of at least LANE_FILL_MIN columns takes the row-tile kernel."""
    monkeypatch.setattr(
        pallas_tpu, "LANE_FILL_MIN",
        1 if request.param == "row_tiles" else 10 ** 9)
    return request.param


def build(groups, record_len, orientation):
    fn = pallas_tpu.build_fused_decode(groups, record_len)
    assert fn.rows_in_lanes == (
        len(groups) if orientation == "rows_in_lanes" else 0)
    return fn


def test_offsets_progression():
    assert pallas_tpu.offsets_progression([10]) == (10, 0)
    assert pallas_tpu.offsets_progression([4, 12, 20]) == (4, 8)
    assert pallas_tpu.offsets_progression([4, 12, 21]) is None
    assert pallas_tpu.offsets_progression([12, 4]) is None
    assert pallas_tpu.offsets_progression([]) is None


def _strided(base, stride, count, width, kind, **kw):
    return pallas_tpu.StridedGroup(
        [base + stride * k for k in range(count)], width, kind, **kw)


def test_binary_group_parity_all_variants(orientation):
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(64, 260), dtype=np.uint8)
    for signed in (False, True):
        for big_endian in (False, True):
            for width, out in [(1, "i32"), (2, "i32"), (3, "i32"),
                               (4, "i32"), (5, "i64"), (8, "i64")]:
                g = _strided(8, 16, 12, width, "binary", out=out,
                             signed=signed, big_endian=big_endian)
                fn = build([g], data.shape[1], orientation)
                (values, valid), = fn(data)
                offs = 8 + 16 * np.arange(12)
                slab = data[:, offs[:, None] + np.arange(width)[None, :]]
                exp_v, exp_ok = batch_np.decode_binary(
                    slab, signed, big_endian)
                np.testing.assert_array_equal(np.asarray(valid), exp_ok)
                np.testing.assert_array_equal(
                    np.asarray(values)[exp_ok], exp_v[exp_ok])


def test_binary_wide_group_parity(orientation):
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(48, 200), dtype=np.uint8)
    for signed in (False, True):
        for width in (9, 12, 16):
            g = _strided(2, 18, 8, width, "binary", out="wide",
                         signed=signed, big_endian=True)
            fn = build([g], data.shape[1], orientation)
            (hi, lo, neg, valid), = fn(data)
            offs = 2 + 18 * np.arange(8)
            slab = data[:, offs[:, None] + np.arange(width)[None, :]]
            e_hi, e_lo, e_neg, e_ok = batch_np.decode_binary_wide(
                slab, signed, True)
            np.testing.assert_array_equal(np.asarray(hi), e_hi)
            np.testing.assert_array_equal(np.asarray(lo), e_lo)
            np.testing.assert_array_equal(np.asarray(neg), e_neg)
            np.testing.assert_array_equal(np.asarray(valid), e_ok)


def test_bcd_group_parity(orientation):
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(32, 260), dtype=np.uint8)
    # make some valid BCD fields
    for i in range(0, 32, 2):
        for k in range(10):
            data[i, 4 + 24 * k:4 + 24 * k + 3] = [0x12, 0x34, 0x5C]
    for width, out in [(2, "i32"), (4, "i32"), (5, "i32"), (6, "i64"),
                       (10, "i64")]:
        g = _strided(4, 24, 10, width, "bcd", out=out)
        fn = build([g], data.shape[1], orientation)
        (values, valid), = fn(data)
        offs = 4 + 24 * np.arange(10)
        slab = data[:, offs[:, None] + np.arange(width)[None, :]]
        exp_v, exp_ok = batch_np.decode_bcd(slab)
        np.testing.assert_array_equal(np.asarray(valid), exp_ok)
        np.testing.assert_array_equal(np.asarray(values)[exp_ok],
                                      exp_v[exp_ok])


def test_bcd_wide_group_parity(orientation):
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, size=(32, 300), dtype=np.uint8)
    for i in range(0, 32, 3):   # seed valid wide fields
        for k in range(6):
            data[i, 3 + 40 * k:3 + 40 * k + 19] = ([0x98, 0x76] * 9
                                                   + [0x5D])
    for width in (11, 19):
        g = _strided(3, 40, 6, width, "bcd", out="wide")
        fn = build([g], data.shape[1], orientation)
        (hi, lo, neg, valid), = fn(data)
        offs = 3 + 40 * np.arange(6)
        slab = data[:, offs[:, None] + np.arange(width)[None, :]]
        e_hi, e_lo, e_neg, e_ok = batch_np.decode_bcd_wide(slab)
        np.testing.assert_array_equal(np.asarray(hi), e_hi)
        np.testing.assert_array_equal(np.asarray(lo), e_lo)
        np.testing.assert_array_equal(np.asarray(neg), e_neg)
        np.testing.assert_array_equal(np.asarray(valid), e_ok)


def _display_cases(rng, n, width, ascii_mode):
    """Byte matrix mixing valid digits, overpunch/sign-separate, dots,
    spaces, and random garbage."""
    if ascii_mode:
        digits = rng.integers(0x30, 0x3A, size=(n, width))
        specials = np.array([0x2D, 0x2B, 0x2E, 0x2C, 0x20, 0x00, 0x41])
    else:
        digits = rng.integers(0xF0, 0xFA, size=(n, width))
        specials = np.array([0x60, 0x4E, 0x4B, 0x6B, 0x40, 0x00, 0xC5,
                             0xD7, 0x7A])
    data = digits.astype(np.uint8)
    # sprinkle specials / garbage
    mask = rng.random((n, width)) < 0.3
    repl = specials[rng.integers(0, len(specials), size=(n, width))]
    data = np.where(mask, repl, data).astype(np.uint8)
    data[: n // 4] = rng.integers(0, 256, size=(n // 4, width))
    return data


@pytest.mark.parametrize("ascii_mode", [False, True])
@pytest.mark.parametrize("width,out", [(3, "i32"), (9, "i32"), (12, "i64"),
                                       (18, "i64"), (22, "wide"),
                                       (38, "wide")])
def test_display_group_parity(orientation, ascii_mode, width, out):
    rng = np.random.default_rng(width * 7 + ascii_mode)
    count = 5
    stride = width + 3
    n = 48
    kind = "display_ascii" if ascii_mode else "display_ebcdic"
    np_narrow = (batch_np.decode_display_ascii if ascii_mode
                 else batch_np.decode_display_ebcdic)
    np_wide = (batch_np.decode_display_ascii_wide if ascii_mode
               else batch_np.decode_display_ebcdic_wide)
    for signed in (False, True):
        for allow_dot, require_digits, dyn_sf in [
                (False, True, 0), (True, True, 0), (False, False, 0),
                (False, False, -2)]:
            data = np.zeros((n, 2 + stride * count), dtype=np.uint8)
            payload = _display_cases(rng, n, width, ascii_mode)
            for k in range(count):
                data[:, 2 + stride * k:2 + stride * k + width] = payload
            g = _strided(2, stride, count, width, kind, out=out,
                         signed=signed, allow_dot=allow_dot,
                         require_digits=require_digits, dyn_sf=dyn_sf)
            fn = build([g], data.shape[1], orientation)
            got, = fn(data)
            offs = 2 + stride * np.arange(count)
            slab = data[:, offs[:, None] + np.arange(width)[None, :]]
            if out == "wide":
                hi, lo, neg, valid, dots = got
                e = np_wide(slab, signed, allow_dot, require_digits, dyn_sf)
                np.testing.assert_array_equal(np.asarray(hi), e[0])
                np.testing.assert_array_equal(np.asarray(lo), e[1])
                np.testing.assert_array_equal(np.asarray(neg), e[2])
                np.testing.assert_array_equal(np.asarray(valid), e[3])
                np.testing.assert_array_equal(np.asarray(dots), e[4])
            else:
                values, valid, dots = got
                e_v, e_ok, e_dots = np_narrow(slab, signed, allow_dot,
                                              require_digits, dyn_sf)
                np.testing.assert_array_equal(np.asarray(valid), e_ok)
                np.testing.assert_array_equal(np.asarray(values)[e_ok],
                                              e_v[e_ok])
                np.testing.assert_array_equal(np.asarray(dots), e_dots)


def test_irregular_offsets_read_their_planes(orientation):
    """Non-progression offsets (exp1-style heterogeneous layouts): the
    row-tile kernel is fed XLA gather planes, the rows-in-lanes kernel
    reads each column's bytes at their own leading indices of the
    transposed matrix, where no layout is irregular."""
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(16, 64), dtype=np.uint8)
    offsets = [0, 7, 19, 40]  # irregular
    g = pallas_tpu.StridedGroup(offsets, 4, "binary", signed=True)
    assert g.progression is None
    fn = build([g], data.shape[1], orientation)
    (values, valid), = fn(data)
    slab = data[:, np.asarray(offsets)[:, None] + np.arange(4)[None, :]]
    e_v, e_ok = batch_np.decode_binary(slab, True, True)
    np.testing.assert_array_equal(np.asarray(values), e_v)
    np.testing.assert_array_equal(np.asarray(valid), e_ok)
    import jax

    gathered = " gather[" in str(jax.make_jaxpr(fn)(data))
    assert gathered == (orientation == "row_tiles")


def test_tail_field_region_past_record_end(orientation):
    """A group whose last field ends at the row boundary must not read out
    of bounds (the wrapper pads the row)."""
    data = np.full((5, 20), 0x00, dtype=np.uint8)
    data[:, 16:20] = 0x01
    g = pallas_tpu.StridedGroup([16], 4, "binary", signed=False)
    fn = build([g], data.shape[1], orientation)
    (values, valid), = fn(data)
    assert np.asarray(values).tolist() == [[0x01010101]] * 5
    # and a field that lies past the matrix it is handed altogether
    (values, valid), = fn(data[:, :18])
    assert np.asarray(values).tolist() == [[0x01010000]] * 5


def test_single_column_groups(orientation):
    """`count` 1, every kind (exp1 has twenty such groups, the TPC-H
    programs and exp2 one each): a column's plane comes back `[b, 1]`."""
    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, size=(40, 48), dtype=np.uint8)
    data[::2, 8:12] = [0x01, 0x23, 0x45, 0x6D]
    data[::3, 20:26] = [0xF1, 0xF2, 0xF3, 0xF4, 0xF5, 0xD6]
    groups = [pallas_tpu.StridedGroup([1], 3, "binary", signed=True),
              pallas_tpu.StridedGroup([8], 4, "bcd"),
              pallas_tpu.StridedGroup([20], 6, "display_ebcdic",
                                      signed=True)]
    binary, bcd, display = build(groups, data.shape[1], orientation)(data)
    e_v, e_ok = batch_np.decode_binary(data[:, None, 1:4], True, True)
    assert np.asarray(binary[0]).shape == (40, 1)
    np.testing.assert_array_equal(np.asarray(binary[0]), e_v)
    e_v, e_ok = batch_np.decode_bcd(data[:, None, 8:12])
    assert e_ok[::2].all()
    np.testing.assert_array_equal(np.asarray(bcd[1]), e_ok)
    np.testing.assert_array_equal(np.asarray(bcd[0])[e_ok], e_v[e_ok])
    e_v, e_ok, e_dots = batch_np.decode_display_ebcdic(
        data[:, None, 20:26], True, False, True, 0)
    assert e_ok[::3].all()
    np.testing.assert_array_equal(np.asarray(display[1]), e_ok)
    np.testing.assert_array_equal(np.asarray(display[0])[e_ok], e_v[e_ok])
    np.testing.assert_array_equal(np.asarray(display[2]), e_dots)


@pytest.mark.parametrize("batch", [1, 127, 4096, 4097, 5000])
def test_batch_no_multiple_of_the_lane_tile(batch):
    """The rows-in-lanes kernel pads the batch to whole grid steps of
    LANE_TILE rows and hands back exactly the rows it was given."""
    assert pallas_tpu.LANE_TILE == 4096
    rng = np.random.default_rng(batch)
    data = rng.integers(0, 256, size=(batch, 24), dtype=np.uint8)
    offsets = [2, 9, 17]
    g = pallas_tpu.StridedGroup(offsets, 5, "binary", out="i64",
                                signed=True)
    fn = pallas_tpu.build_fused_decode([g], data.shape[1])
    assert fn.rows_in_lanes == 1
    (values, valid), = fn(data)
    slab = data[:, np.asarray(offsets)[:, None] + np.arange(5)[None, :]]
    e_v, e_ok = batch_np.decode_binary(slab, True, True)
    assert np.asarray(values).shape == (batch, 3)
    np.testing.assert_array_equal(np.asarray(values), e_v)
    np.testing.assert_array_equal(np.asarray(valid), e_ok)


def _mixed_program():
    """A `count`-2000 OCCURS group between a `count`-1 and a `count`-3
    group: the first fills the lanes, the others do not."""
    wide = _strided(40, 6, 2000, 4, "binary", signed=True)
    one = pallas_tpu.StridedGroup([3], 5, "bcd", out="i32")
    three = pallas_tpu.StridedGroup([10, 21, 29], 8, "binary", out="i64",
                                    signed=False)
    return [one, wide, three]


def _check_mixed(results, data):
    (bcd_v, bcd_ok), (wide_v, wide_ok), (three_v, three_ok) = results
    offs = 40 + 6 * np.arange(2000)
    e_v, e_ok = batch_np.decode_binary(
        data[:, offs[:, None] + np.arange(4)[None, :]], True, True)
    np.testing.assert_array_equal(np.asarray(wide_v), e_v)
    np.testing.assert_array_equal(np.asarray(wide_ok), e_ok)
    e_v, e_ok = batch_np.decode_bcd(data[:, None, 3:8])
    np.testing.assert_array_equal(np.asarray(bcd_ok), e_ok)
    np.testing.assert_array_equal(np.asarray(bcd_v)[e_ok], e_v[e_ok])
    offs = np.asarray([10, 21, 29])
    e_v, e_ok = batch_np.decode_binary(
        data[:, offs[:, None] + np.arange(8)[None, :]], False, True)
    np.testing.assert_array_equal(np.asarray(three_ok), e_ok)
    np.testing.assert_array_equal(np.asarray(three_v)[e_ok], e_v[e_ok])


def test_program_of_both_orientations():
    """One program, two pallas_calls, one result list in group order."""
    import jax

    rng = np.random.default_rng(77)
    data = rng.integers(0, 256, size=(70, 40 + 6 * 2000), dtype=np.uint8)
    data[::2, 3:8] = [0x00, 0x12, 0x34, 0x56, 0x7C]
    groups = _mixed_program()
    fn = pallas_tpu.build_fused_decode(groups, data.shape[1])
    assert fn.rows_in_lanes == 2
    assert str(jax.make_jaxpr(fn)(data)).count("pallas_call[") == 2
    _check_mixed(fn(data), data)


def test_narrow_groups_past_the_vector_memory(monkeypatch):
    """Narrow groups that read and write more a row than the kernel's
    blocks may hold are cut into several calls, in order."""
    import jax

    rng = np.random.default_rng(78)
    data = rng.integers(0, 256, size=(33, 40 + 6 * 2000), dtype=np.uint8)
    groups = _mixed_program()
    # the bcd group costs 5 + 2 * 4 B a row, the 8-byte one 3 * (8 + 5 * 4)
    assert pallas_tpu._lane_calls([0, 2], groups) == [[0, 2]]
    monkeypatch.setattr(pallas_tpu, "LANE_ROW_BYTES_MAX", 90)
    assert pallas_tpu._lane_calls([0, 2], groups) == [[0], [2]]
    fn = pallas_tpu.build_fused_decode(groups, data.shape[1])
    assert fn.rows_in_lanes == 2
    assert str(jax.make_jaxpr(fn)(data)).count("pallas_call[") == 3
    _check_mixed(fn(data), data)


def test_fused_coverage_fraction():
    """VERDICT r2 ask #3: the fraction of decoded bytes flowing through
    the fused kernel must exceed 90% of numeric+string bytes on the exp1
    and exp3 plans (strings ride the XLA LUT-gather inside the same
    program; floats are the only other non-fused plane)."""
    from cobrix_tpu.plan.compiler import Codec
    from cobrix_tpu.reader.columnar import _FLOAT_CODECS, _STRING_CODECS

    for name, cb, active in [
            ("exp1", parse_copybook(EXP1_COPYBOOK), None),
            ("exp3C", parse_copybook(
                EXP3_COPYBOOK,
                segment_redefines=["STATIC-DETAILS", "CONTACTS"]),
             "STATIC_DETAILS")]:
        dec = ColumnarDecoder(cb, backend="pallas", active_segment=active)
        fused = sum(len(g.columns) * g.width for g in dec.kernel_groups
                    if _pallas_group_spec(g) is not None)
        numeric_string = sum(
            len(g.columns) * g.width for g in dec.kernel_groups
            if g.codec not in _FLOAT_CODECS
            and g.codec is not Codec.HOST_FALLBACK)
        total = sum(len(g.columns) * g.width for g in dec.kernel_groups)
        frac = fused / numeric_string
        assert frac > 0.90, (name, frac)
        # and nothing decodes per record on the host for these plans
        assert not any(g.codec is Codec.HOST_FALLBACK
                       for g in dec.kernel_groups), name
        print(f"{name}: fused {fused}/{numeric_string} "
              f"({100 * frac:.1f}% of numeric+string bytes; "
              f"total plan bytes {total})")


class TestColumnarPallasBackend:
    """End-to-end: ColumnarDecoder(backend='pallas') == backend='numpy'."""

    @pytest.fixture(scope="class")
    def copybook(self):
        return parse_copybook(EXP3_COPYBOOK)

    def test_exp3_wide_segment_parity(self, copybook):
        # frame the RDW stream on host and keep the wide 'C' records
        raw = generate_exp3(60, seed=11)
        records, pos = [], 0
        while pos < len(raw):
            length = raw[pos + 2] | (raw[pos + 3] << 8)
            records.append(raw[pos + 4:pos + 4 + length])
            pos += 4 + length
        wide = [r for r in records if len(r) > 1000]
        assert len(wide) >= 10
        arr = np.frombuffer(b"".join(wide), dtype=np.uint8).reshape(
            len(wide), -1)
        dec_p = ColumnarDecoder(copybook, backend="pallas")
        dec_n = ColumnarDecoder(copybook, backend="numpy")
        # the wide numeric groups must actually take the fused kernel
        assert sum(1 for g in dec_p.kernel_groups
                   if _pallas_group_spec(g) is not None) >= 2
        out_p = dec_p.decode(arr)
        out_n = dec_n.decode(arr)
        for c in dec_p.plan.columns:
            for i in range(arr.shape[0]):
                assert out_p.value(c.index, i) == out_n.value(c.index, i), \
                    f"column {c.name} record {i}"

    def test_exp1_full_profile_parity(self):
        """All 195 exp1 fields through the pallas backend == numpy, on
        valid generated data plus a malformed tail."""
        cb = parse_copybook(EXP1_COPYBOOK)
        data = generate_exp1(24, seed=13)
        rng = np.random.default_rng(14)
        junk = rng.integers(0, 256, size=(8, data.shape[1]), dtype=np.uint8)
        arr = np.concatenate([data, junk])
        dec_p = ColumnarDecoder(cb, backend="pallas")
        dec_n = ColumnarDecoder(cb, backend="numpy")
        out_p = dec_p.decode(arr)
        out_n = dec_n.decode(arr)
        for c in dec_p.plan.columns:
            for i in range(arr.shape[0]):
                assert out_p.value(c.index, i) == out_n.value(c.index, i), \
                    f"column {c.name} record {i}"
