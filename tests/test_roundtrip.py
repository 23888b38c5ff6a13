"""Round-trip properties of the copybook-driven encoder.

The write half (cobrix_tpu.encode) must stay byte-compatible with the
readers: encode→decode is value-identical over the canonical domain,
and decode→encode reproduces the file byte for byte (the properties
tools/rtcheck.py fuzzes). The non-slow matrix here pins the named
grammar surface — fixed/RDW framing × DISPLAY/COMP/COMP-3/float ×
every sign flavor × two code pages × OCCURS and DEPENDING ON — plus
the permissive-policy corrupt-record loop; the random sweep (≥100
copybooks with shrinking) runs under the `slow` marker.
"""
import os
import sys
from decimal import Decimal

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from cobrix_tpu import read_cobol  # noqa: E402
from cobrix_tpu.encode import (  # noqa: E402
    BatchEncoder,
    EncodeError,
    RecordEncoder,
    encode_field,
    encode_file,
)
from cobrix_tpu.testing import corpus  # noqa: E402
from cobrix_tpu.testing.genspec import CopybookSpec, safe_alphabet  # noqa: E402

import rtcheck  # noqa: E402  (tools/rtcheck.py — the property harness)


def _roundtrip(tmp_path, copybook, bodies, framing="fixed",
               encode_kw=None, read_kw=None, reencode_kw=None):
    """Assert P1 (value identity) and P2 (byte stability); return rows."""
    data = encode_file(copybook, bodies, framing=framing,
                       **(encode_kw or {}))
    path = str(tmp_path / "rt.dat")
    with open(path, "wb") as f:
        f.write(data)
    kw = dict(copybook_contents=copybook)
    if framing == "rdw":
        kw["is_record_sequence"] = "true"
    kw.update(read_kw or {})
    out = read_cobol(path, **kw)
    rows = out.to_rows()
    assert [list(r) for r in rows] == [list(b) for b in bodies]
    assert out.to_ebcdic(framing=framing, **(reencode_kw or {})) == data
    return rows


SCALAR_COPYBOOK = """
       01  REC.
           05  NUM-DISP     PIC S9(5)V99.
           05  NUM-BIN      PIC S9(8)  COMP.
           05  NUM-BIN-LE   PIC 9(4)   COMP-9.
           05  NUM-BCD      PIC S9(7)V9(2) COMP-3.
           05  NUM-BCD-WIDE PIC S9(21) COMP-3.
           05  FLT-SINGLE   COMP-1.
           05  FLT-DOUBLE   COMP-2.
           05  NAME         PIC X(8).
"""

SCALAR_BODIES = [
    [(Decimal("-123.45"), -12345678, 9999, Decimal("98765.43"),
      -10 ** 20 - 7, 2.5, -1234.0625, "Ab.9-Z")],
    [(Decimal("0.00"), 0, 0, Decimal("0.00"), 0, 0.0, 0.0, "")],
    # None is canonical everywhere blank fill can express it — including
    # the implied-point DISPLAY decimal (blank decodes to null, not 0.00)
    [(None, 1, 1, None, None, 1.5, -0.25, "x")],
    [(Decimal("-0.07"), 2, 2, Decimal("0.01"), 5, 0.5, 2.0, "y")],
]


@pytest.mark.parametrize("framing", ["fixed", "rdw"])
@pytest.mark.parametrize("code_page",
                         ["common", "cp037", "cp500", "cp1047"])
def test_scalar_matrix(tmp_path, framing, code_page):
    """DISPLAY/COMP/COMP-9/COMP-3 (narrow + wide)/COMP-1/COMP-2/X
    across framings and code pages."""
    from cobrix_tpu.copybook.datatypes import FloatingPointFormat

    _roundtrip(
        tmp_path, SCALAR_COPYBOOK, SCALAR_BODIES, framing,
        encode_kw=dict(ebcdic_code_page=code_page,
                       floating_point_format=FloatingPointFormat.IEEE754),
        read_kw=dict(ebcdic_code_page=code_page,
                     floating_point_format="ieee754"))


SIGN_COPYBOOK = """
       01  REC.
           05  TRAIL-OVER   PIC S9(4).
           05  LEAD-OVER    PIC S9(4) SIGN IS LEADING.
           05  LEAD-SEP     PIC S9(4) SIGN IS LEADING SEPARATE.
           05  TRAIL-SEP    PIC S9(4) SIGN IS TRAILING SEPARATE.
           05  EXPL-DOT     PIC S9(3).9(2).
           05  UNSIGNED     PIC 9(4).
"""


@pytest.mark.parametrize("framing", ["fixed", "rdw"])
def test_sign_variants(tmp_path, framing):
    bodies = [
        [(-42, -42, -42, -42, Decimal("-1.25"), 42)],
        [(42, 42, 42, 42, Decimal("1.25"), 0)],
        [(0, 0, 0, 0, None, 9999)],
    ]
    _roundtrip(tmp_path, SIGN_COPYBOOK, bodies, framing)


OCCURS_COPYBOOK = """
       01  REC.
           05  ID      PIC 9(4) COMP.
           05  POINTS  PIC S9(3)V9 COMP-3 OCCURS 3 TIMES.
           05  PAIR    OCCURS 2 TIMES.
              10  TAG   PIC X(3).
              10  VAL   PIC 9(2).
"""


def test_static_occurs(tmp_path):
    bodies = [
        [(1, [Decimal("1.5"), Decimal("-2.5"), Decimal("0.0")],
          [("abc", 1), ("de", 22)])],
        [(2, [None, Decimal("99.9"), None], [("", 0), ("zz", 7)])],
    ]
    _roundtrip(tmp_path, OCCURS_COPYBOOK, bodies)


ODO_COPYBOOK = """
       01  REC.
           05  ID      PIC 9(4) COMP.
           05  CNT     PIC 9(2).
           05  ITEM    PIC S9(5) COMP-3 OCCURS 0 TO 4 TIMES
               DEPENDING ON CNT.
           05  TAIL    PIC X(4).
"""


def test_depending_on_variable_records(tmp_path):
    bodies = [
        [(1, 3, [11, -22, 33], "aaaa")],
        [(2, 0, [], "bb")],
        [(3, 4, [1, 2, 3, 4], "")],
    ]
    _roundtrip(
        tmp_path, ODO_COPYBOOK, bodies, "rdw",
        encode_kw=dict(variable_size_occurs=True),
        read_kw=dict(variable_size_occurs="true"),
        reencode_kw=dict(variable_size_occurs=True))


def test_multiseg_redefines(tmp_path):
    """Segment-gated redefines: inactive branches are None both ways."""
    bodies = [
        [("C", "C000000001", ("Acme Ltd.", 12345678), None)],
        [("P", "C000000001", None, ("+0123456789", "Jane Roe"))],
        [("P", "C000000001", None, ("+0987654321", "Sam Poe"))],
        [("C", "C000000002", ("Globex", 999), None)],
    ]
    data = encode_file(
        corpus.MULTISEG_COPYBOOK, bodies, framing="rdw",
        segment_redefines=["STATIC-DETAILS", "CONTACTS"])
    path = str(tmp_path / "seg.dat")
    with open(path, "wb") as f:
        f.write(data)
    out = read_cobol(path, **corpus.multiseg_read_options())
    rows = out.to_rows()
    assert [list(r) for r in rows] == [list(b) for b in bodies]
    assert out.to_ebcdic(framing="rdw") == data


@pytest.mark.parametrize("write, options, framing", [
    (corpus.write_fixed_corpus, corpus.fixed_read_options, "fixed"),
    (corpus.write_multiseg_corpus, corpus.multiseg_read_options, "rdw"),
], ids=["fixed", "multiseg"])
def test_factory_corpus_reencodes_to_its_own_bytes(tmp_path, write,
                                                   options, framing):
    """What testing/corpus.py promises of every file it writes: the
    decoded rows encode back to exactly the file's bytes."""
    path = str(tmp_path / "corpus.dat")
    write(path, 2000, seed=100)
    with open(path, "rb") as f:
        data = f.read()
    assert read_cobol(path, **options()).to_ebcdic(framing=framing) == data


def test_permissive_corrupt_record_roundtrip(tmp_path):
    """Encoder-aware damage + permissive policy: the damaged field
    decodes to None, and decode→encode→decode is stable (the re-encoded
    file decodes to the same rows — corrupt nibbles normalize to blank
    fill, which still decodes to None)."""
    path = str(tmp_path / "txn.dat")
    corpus.write_fixed_corpus(path, 300, seed=5)
    data = open(path, "rb").read()
    bad, sites = corpus.corrupt_fixed_corpus(
        data, count=2, seed=9, kinds=("sign-nibble", "packed-digit"))
    with open(path, "wb") as f:
        f.write(bad)
    out = read_cobol(path, **corpus.fixed_read_options(),
                     record_error_policy="permissive")
    rows = out.to_rows()
    for site in sites:
        assert rows[site["record"]][0][3] is None, site
    re_encoded = out.to_ebcdic(framing="fixed")
    path2 = str(tmp_path / "txn2.dat")
    with open(path2, "wb") as f:
        f.write(re_encoded)
    rows2 = read_cobol(path2, **corpus.fixed_read_options(),
                       record_error_policy="permissive").to_rows()
    assert rows2 == rows


def test_batch_encoder_matches_record_encoder():
    """The vectorized column path and the record-at-a-time walker must
    emit identical bytes for a static layout."""
    enc = RecordEncoder(corpus.TXN_COPYBOOK)
    batch = BatchEncoder(corpus.TXN_COPYBOOK)
    bodies = [
        [(7, "ACC0000001", "USD", Decimal("-12345.67"),
          Decimal("999.99"), "A", 42)],
        [(8, "", "EUR", Decimal("0.00"), Decimal("-0.01"), "D", 0)],
    ]
    record_bytes = b"".join(enc.encode_record(b) for b in bodies)
    cols = [
        [7, 8], ["ACC0000001", ""], ["USD", "EUR"],
        [-1234567, 0], [99999, -1], ["A", "D"], [42, 0],
    ]
    assert batch.encode_fixed(cols, 2) == record_bytes


def test_encoder_refuses_out_of_domain():
    from cobrix_tpu.copybook.copybook import parse_copybook

    cb = parse_copybook("""
       01  REC.
           05  N  PIC 9(2).
           05  S  PIC X(2).
    """)
    fields = {st.name: st.dtype for st in cb.ast.walk_primitives()}
    n = fields["N"]
    s = fields["S"]
    with pytest.raises(EncodeError):
        encode_field(n, 100)   # 3 digits into PIC 9(2)
    with pytest.raises(EncodeError):
        encode_field(n, -1)    # negative into unsigned
    with pytest.raises(EncodeError):
        encode_field(s, "abc")  # 3 chars into X(2)


def test_safe_alphabet_round_trips_per_code_page():
    from cobrix_tpu.encoding.codepages import (
        get_code_page_encode_table,
        get_code_page_table,
    )

    for cp in ("common", "cp037", "cp500", "cp1047"):
        table = get_code_page_table(cp)
        enc = get_code_page_encode_table(cp)
        for ch in safe_alphabet(cp):
            assert table[enc[ch]] == ch


def test_cp500_matches_stdlib_codec():
    """The cp500 table's printable region must agree with Python's own
    cp500 codec position by position (the control region follows the
    repo-wide convention the cp037 tables established instead)."""
    from cobrix_tpu.encoding.codepages import get_code_page_table

    table = get_code_page_table("cp500_extended")
    ours_037 = get_code_page_table("cp037_extended")
    for byte in range(0x40, 0x100):
        want = bytes([byte]).decode("cp500")
        # positions the repo cp037 table already diverges from stdlib
        # cp037 on (deliberate reference-compat choices) carry over
        if ours_037[byte] == bytes([byte]).decode("cp037"):
            assert table[byte] == want, (hex(byte), table[byte], want)


def test_cp1047_bracket_rotation():
    """cp1047's signature cells (the z/OS Open Systems bracket layout)
    sit where IBM-1047 puts them, and everything else matches cp037."""
    from cobrix_tpu.encoding.codepages import get_code_page_table

    t1047 = get_code_page_table("cp1047_extended")
    t037 = get_code_page_table("cp037_extended")
    rotated = {0x5F: "^", 0xAD: "[", 0xB0: "\xac", 0xBA: "\xdd",
               0xBB: "\xa8", 0xBD: "]"}
    for byte in range(256):
        want = rotated.get(byte, t037[byte])
        assert t1047[byte] == want, (hex(byte), t1047[byte], want)


def test_duplicate_glyph_encode_is_lowest_byte_wins():
    """Every glyph that several EBCDIC bytes decode to must encode to
    the LOWEST of those bytes on every builtin page — the deterministic
    inversion that makes decode→encode→decode byte-stable once the
    aliases canonicalize (rtcheck P3 covers the end-to-end surface)."""
    from cobrix_tpu.encoding.codepages import (
        get_code_page_encode_table,
        get_code_page_table,
    )

    for cp in rtcheck.ALIAS_CODE_PAGES:
        table = get_code_page_table(cp)
        enc = get_code_page_encode_table(cp)
        first_byte = {}
        duplicated = set()
        for byte in range(256):
            ch = table[byte]
            if ch in first_byte:
                duplicated.add(ch)
            else:
                first_byte[ch] = byte
        assert duplicated, cp  # every builtin page carries alias glyphs
        for ch in duplicated:
            want = 0x40 if ch == " " else first_byte[ch]
            assert enc[ch] == want, (cp, ch, hex(enc[ch]))


def test_rtcheck_alias_matrix():
    """P3: raw alias bytes canonicalize in one decode→encode round on
    every builtin code page."""
    assert rtcheck.run_alias_matrix(seeds=(0,)) == 0


def test_rtcheck_quick_harness():
    """Tier-1 anchor: the deterministic rtcheck matrix stays green
    (--sweep runs under the slow marker)."""
    assert rtcheck.run_quick() == 0


@pytest.mark.slow
def test_rtcheck_sweep():
    """≥100 random copybooks; failures would print shrunk repros."""
    assert rtcheck.run_sweep(120, base_seed=5000) == 0
