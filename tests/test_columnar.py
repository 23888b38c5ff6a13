"""Columnar (batched) decode path tests.

The columnar path is validated three ways (SURVEY.md §4 adapted):
1. golden parity — same JSON output as the reference goldens,
2. oracle parity — same rows as the host extractor on random/adversarial bytes,
3. both backends (numpy and jax-on-CPU-mesh) agree.
"""
import glob
import os

import numpy as np
import pytest

from cobrix_tpu import parse_copybook
from cobrix_tpu.copybook.datatypes import SchemaRetentionPolicy
from cobrix_tpu.reader.columnar import ColumnarDecoder
from cobrix_tpu.reader.extractors import extract_record
from cobrix_tpu.reader.json_out import rows_to_json
from cobrix_tpu.reader.schema import CobolOutputSchema

from util import read_binary, read_copybook, read_golden_lines

BACKENDS = ("numpy", "jax")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("cob,datafile,exp,genid", [
    ("test1_copybook.cob", "test1_data", "test1_expected/test1.txt", False),
    ("test19_display_num.cob", "test19_display_num",
     "test19_display_num_expected/test19.txt", True),
])
def test_columnar_golden_parity(backend, cob, datafile, exp, genid):
    cb = parse_copybook(read_copybook(cob))
    data = read_binary(datafile)
    schema = CobolOutputSchema(cb, policy=SchemaRetentionPolicy.COLLAPSE_ROOT,
                               generate_record_id=genid)
    dec = ColumnarDecoder(cb, backend=backend)
    rows = dec.decode(data).to_rows(policy=SchemaRetentionPolicy.COLLAPSE_ROOT,
                                    generate_record_id=genid)
    actual = rows_to_json(rows, schema.schema)
    assert actual == read_golden_lines(exp)


FUZZ_COPYBOOK = """
       01  REC.
           05  NAME        PIC X(6).
           05  CNT         PIC 9(2).
           05  ITEMS       OCCURS 1 TO 3 TIMES DEPENDING ON CNT.
               10  QTY     PIC S9(4) COMP.
               10  PRICE   PIC S9(5)V99 COMP-3.
               10  TAG     PIC X(3).
           05  RATE        PIC S9(3)V9(2).
           05  FLAGS       PIC 9(4) COMP-5.
           05  BAL         PIC S9(9)V99 COMP-3.
           05  FVAL        COMP-1.
           05  DVAL        COMP-2.
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_columnar_matches_host_extractor_on_fuzz(backend):
    cb = parse_copybook(FUZZ_COPYBOOK)
    rs = cb.record_size
    rng = np.random.default_rng(42)
    n = 300
    data = rng.integers(0, 256, size=(n, rs), dtype=np.uint8)
    # mix in plausible EBCDIC digits/spaces to hit the valid paths too
    half = n // 2
    digits = rng.integers(0xF0, 0xFA, size=(half, rs), dtype=np.uint8)
    spaces = rng.random(size=(half, rs)) < 0.2
    data[:half] = np.where(spaces, 0x40, digits)
    # CNT within range for the first half
    data[:half, 6] = 0xF0
    data[:half, 7] = rng.integers(0xF0, 0xF4, size=half, dtype=np.uint8)

    dec = ColumnarDecoder(cb, backend=backend)
    batch = dec.decode(data)
    rows_columnar = batch.to_rows()
    for i in range(n):
        expected = extract_record(cb.ast, data[i].tobytes())
        assert rows_columnar[i] == expected, (
            f"record {i}: {rows_columnar[i]!r} != {expected!r}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_occurs_depending_on_gating(backend):
    cb = parse_copybook(FUZZ_COPYBOOK)
    rs = cb.record_size
    rec = bytearray(b"\x40" * rs)
    rec[0:6] = "ABC".ljust(6).encode("ascii")  # will be decoded via EBCDIC
    rec[6:8] = bytes([0xF0, 0xF2])  # CNT = 2
    dec = ColumnarDecoder(cb, backend=backend)
    batch = dec.decode(bytes(rec))
    rows = batch.to_rows()
    items = rows[0][0][2]
    assert len(items) == 2  # gated by CNT, not max size


@pytest.mark.jax
def test_backends_agree_on_goldens():
    cb = parse_copybook(read_copybook("test1_copybook.cob"))
    data = read_binary("test1_data")
    rows = {}
    for backend in BACKENDS:
        dec = ColumnarDecoder(cb, backend=backend)
        rows[backend] = dec.decode(data).to_rows()
    assert rows["numpy"] == rows["jax"]


@pytest.mark.jax
def test_jit_bucket_padding():
    cb = parse_copybook(FUZZ_COPYBOOK)
    rs = cb.record_size
    dec = ColumnarDecoder(cb, backend="jax")
    data = np.full((3, rs), 0x40, dtype=np.uint8)
    batch = dec.decode(data)
    assert batch.n_records == 3
    assert len(batch.to_rows()) == 3


@pytest.mark.jax
@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_decode_raw_segment_masks_match_full_decode(backend, monkeypatch):
    """decode_raw(segment_row_masks=...) decodes each masked group only on
    its own rows; visible rows must match the unmasked decode exactly and
    hidden rows must come back invalid (None), not as decoded garbage.
    The host kernels subset-decode and scatter at once; a device backend
    launches by redefine and scatters a group when it is asked for by
    position (these widths spare the link too little to engage unasked)."""
    import numpy as np

    from cobrix_tpu import parse_copybook
    from cobrix_tpu.reader import columnar
    from cobrix_tpu.reader.columnar import ColumnarDecoder

    monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 0)

    cb = parse_copybook("""
       01 R.
          05 SEG-ID    PIC X(1).
          05 A-SEG.
             10 BIGN   PIC S9(12)V99 COMP-3.
             10 WIDE   PIC 9(10).
          05 B-SEG REDEFINES A-SEG.
             10 NUM    PIC S9(8) COMP.
             10 TXT    PIC X(14).
    """, segment_redefines=["A-SEG", "B-SEG"])
    from cobrix_tpu.testing.generators import ebcdic_encode

    recs = []
    for i in range(40):
        if i % 3 == 0:
            body = (bytes.fromhex(f"{i * 100:013d}c")
                    + ebcdic_encode(f"{i:010d}"))
            recs.append(ebcdic_encode("A") + body)
        else:
            recs.append(ebcdic_encode("B") + i.to_bytes(4, "big", signed=True)
                        + ebcdic_encode(f"person-{i:05d}", 14))
    data = b"".join(recs)
    n = len(recs)
    rs = cb.record_size
    offsets = np.arange(n, dtype=np.int64) * rs
    lengths = np.full(n, rs, dtype=np.int64)
    a_mask = np.array([i % 3 == 0 for i in range(n)])
    masks = {"A_SEG": a_mask, "B_SEG": ~a_mask}

    dec = ColumnarDecoder(cb)
    full = dec.decode_raw(data, offsets, lengths)
    dec2 = ColumnarDecoder(cb, backend=backend)
    masked = dec2.decode_raw(data, offsets, lengths,
                             segment_row_masks=masks)
    upper_masks = {k.upper(): v for k, v in masks.items()}
    from cobrix_tpu.reader.columnar import _STRING_CODECS
    if backend == "numpy":
        engaged = {c.index
                   for g in dec2.kernel_groups
                   if g.codec not in _STRING_CODECS
                   and dec2._group_segment_mask(g, upper_masks) is not None
                   for c in g.columns}
    else:
        # every group of a redefine came back for its own rows alone
        subsets = {c.index for c in dec2.plan.columns
                   if "subset" in masked._out[c.index]}
        assert subsets == {c.index for c in dec2.plan.columns if c.segment}
        for index in subsets:
            part = masked._out[index]["subset"]
            assert part.mask is masks[part.group.segment]
        engaged = {c.index for c in dec2.plan.columns
                   if c.segment and c.codec not in _STRING_CODECS}
    assert engaged, "heuristic should engage at least one group"
    for c in dec.plan.columns:
        seg = (c.segment or "").upper()
        vis = masks.get(seg)
        fv = full.column_values(c.index)
        mv = masked.column_values(c.index)
        for i in range(n):
            if vis is None or vis[i]:
                assert mv[i] == fv[i], (c.name, i, mv[i], fv[i])
            elif c.index in engaged:
                # hidden rows of a masked group come back invalid,
                # never as decoded garbage
                assert mv[i] is None, (c.name, i, mv[i])
