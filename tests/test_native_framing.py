"""Native (C++) framing/packing vs Python fallbacks and reader parity."""
import numpy as np
import pytest

from cobrix_tpu import native
from cobrix_tpu.testing.generators import (ebcdic_encode, generate_exp2,
                                           generate_exp3)


def _rdw_le(n: int) -> bytes:
    return bytes([0, 0, n & 0xFF, n >> 8])


def _rdw_be(n: int) -> bytes:
    return bytes([n >> 8, n & 0xFF, 0, 0])


def test_native_builds():
    assert native.available(), "C++ framing library failed to build"


@pytest.mark.parametrize("big_endian", [False, True])
def test_rdw_scan_parity(big_endian):
    mk = _rdw_be if big_endian else _rdw_le
    payloads = [b"A" * 10, b"B" * 3, b"C" * 300, b"D"]
    data = b"".join(mk(len(p)) + p for p in payloads)
    offs, lens = native.rdw_scan(data, big_endian=big_endian)
    assert list(lens) == [10, 3, 300, 1]
    for off, ln, p in zip(offs, lens, payloads):
        assert data[off:off + ln] == p


def test_rdw_scan_matches_exp2_generator():
    raw = generate_exp2(500, seed=7)
    offs, lens = native.rdw_scan(raw, big_endian=False)
    assert len(offs) == 500
    assert set(lens) <= {60, 64, 68}


def _walk_rdw(raw: bytes, big_endian: bool, adjustment: int = 0):
    """The RDW chain walked header by header, in plain Python: payload
    offsets and lengths, the last length cut to the bytes present."""
    offsets, lengths = [], []
    pos = 0
    while pos + 4 <= len(raw):
        if big_endian:
            n = raw[pos] << 8 | raw[pos + 1]
        else:
            n = raw[pos + 3] << 8 | raw[pos + 2]
        n += adjustment
        offsets.append(pos + 4)
        lengths.append(min(n, len(raw) - (pos + 4)))
        pos += 4 + n
    return offsets, lengths


# length stored +4 (the RDW counts itself): read with rdw_adjustment=-4
_ADJUSTED = b"".join(_rdw_be(len(r) + 4) + r
                     for r in (b"ABCD", b"EFGHIJ", b"XY"))
# the tail record declares 8 bytes and holds 2
_TRUNCATED = _rdw_le(4) + b"ABCD" + _rdw_le(8) + b"EF"


@pytest.mark.parametrize("image, scan_kw, expected", [
    (lambda: generate_exp2(400, seed=11), dict(big_endian=False), None),
    (lambda: generate_exp2(400, seed=11, big_endian_rdw=True),
     dict(big_endian=True), None),
    (lambda: generate_exp3(40, seed=11), dict(big_endian=False), None),
    (lambda: _ADJUSTED, dict(big_endian=True, rdw_adjustment=-4),
     ([4, 12, 22], [4, 6, 2])),
    (lambda: _TRUNCATED, dict(big_endian=False), ([4, 12], [4, 2])),
    (lambda: b"", dict(big_endian=False), ([], [])),
    (lambda: b"\x00\x00", dict(big_endian=False), ([], [])),
], ids=["exp2-le", "exp2-be", "exp3-wide", "adjustment", "truncated-tail",
        "empty", "two-bytes"])
def test_rdw_scan_matches_header_walk(image, scan_kw, expected):
    """The host scan that frames every cell against a header walk that
    imports nothing of the product (or the literal, where one is short
    enough to state)."""
    raw = image()
    walked = _walk_rdw(raw, scan_kw["big_endian"],
                       scan_kw.get("rdw_adjustment", 0))
    if expected is not None:
        assert walked == expected
    offs, lens = native.rdw_scan(raw, **scan_kw)
    assert (offs.tolist(), lens.tolist()) == walked


def test_rdw_zero_header_raises():
    data = _rdw_le(5) + b"XXXXX" + bytes(4)
    with pytest.raises(ValueError, match="zero"):
        native.rdw_scan(data, big_endian=False)


def test_rdw_header_footer_regions():
    data = (b"HEADER" + _rdw_le(4) + b"AAAA" + _rdw_le(4) + b"BBBB"
            + b"FOOTER42")
    offs, lens = native.rdw_scan(data, big_endian=False,
                                 file_header_bytes=6, file_footer_bytes=8)
    assert list(lens) == [4, 4]
    assert data[offs[0]:offs[0] + 4] == b"AAAA"


def test_length_field_scan_binary_be():
    # records: [len:2 BE][payload]; length includes the field itself
    recs = [b"\x00\x06ABCD", b"\x00\x03X", b"\x00\x08PQRSTU"]
    data = b"".join(recs)
    offs, lens, resume = native.length_field_scan(
        data, field_offset=0, field_width=2,
        kind=native.LENGTH_FIELD_BINARY_BE)
    assert list(lens) == [6, 3, 8]
    assert resume == len(data)


def test_length_field_scan_display_ebcdic_stops_on_garbage():
    recs = [ebcdic_encode("05") + b"ABC", ebcdic_encode("07") + b"DEFGH"]
    data = b"".join(recs) + b"\x7a\x00"  # non-digit garbage tail
    offs, lens, resume = native.length_field_scan(
        data, field_offset=0, field_width=2,
        kind=native.LENGTH_FIELD_DISPLAY_EBCDIC)
    assert list(lens) == [5, 7]
    assert resume == 12  # garbage tail position reported


def test_text_scan():
    data = b"alpha\nbeta\r\ngamma"
    offs, lens = native.text_scan(data)
    got = [bytes(np.frombuffer(data, np.uint8)[o:o + l]).decode()
           for o, l in zip(offs, lens)]
    assert got == ["alpha", "beta", "gamma"]


def test_pack_records_pads_and_truncates():
    data = b"0123456789"
    offs = np.array([0, 4, 8], dtype=np.int64)
    lens = np.array([4, 4, 2], dtype=np.int64)
    out = native.pack_records(data, offs, lens, extent=3)
    assert out.tolist() == [[48, 49, 50], [52, 53, 54], [56, 57, 0]]
    out = native.pack_records(data, offs, lens, extent=5)
    assert out[2].tolist() == [56, 57, 0, 0, 0]
    out = native.pack_records(data, offs, lens, extent=4, start_offset=1)
    assert out[0].tolist() == [49, 50, 51, 0]


def test_python_fallback_parity(monkeypatch):
    """The NumPy fallbacks produce identical results to the C++ paths."""
    raw = generate_exp2(100, seed=9)
    offs_c, lens_c = native.rdw_scan(raw, big_endian=False)
    packed_c = native.pack_records(raw, offs_c, lens_c, extent=68)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", True)
    assert not native.available()
    offs_p, lens_p = native.rdw_scan(raw, big_endian=False)
    packed_p = native.pack_records(raw, offs_p, lens_p, extent=68)
    assert np.array_equal(offs_c, offs_p)
    assert np.array_equal(lens_c, lens_p)
    assert np.array_equal(packed_c, packed_p)
