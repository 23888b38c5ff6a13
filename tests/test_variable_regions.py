"""Variable-size OCCURS DEPENDING ON records (`variable_size_occurs`)
through the batched path: the plan's regions (plan/compiler.py), the
two-pass expansion (ops/expand.py) on the host kernels and inside the
device program, lists of structs built record-major, and what stays on
the record walk, counted. Every read case holds the batched table to the
scalar oracle's (`backend="host"`, the record walk) bit for bit, nulls
and list lengths included, on seeded bytes from
`RecordEncoder(variable_size_occurs=True)`."""
import decimal
import random

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from cobrix_tpu import read_cobol
from cobrix_tpu.copybook.copybook import parse_copybook
from cobrix_tpu.encode import RecordEncoder
from cobrix_tpu.ops import batch_np, expand
from cobrix_tpu.plan.compiler import (VariableRegion, array_of_arrays,
                                      compile_plan)
from cobrix_tpu.reader import columnar

BATCHED = ("numpy", "jax", "pallas")
D = decimal.Decimal

MID = """
       01  REC.
           05  ID        PIC S9(9) COMP.
           05  CNT       PIC 9(1).
           05  ITEMS OCCURS 0 TO 5 TIMES DEPENDING ON CNT.
               10  QTY   PIC S9(4) COMP.
               10  AMT   PIC S9(5)V99 COMP-3.
               10  NAME  PIC X(6).
           05  TAIL      PIC X(10).
           05  TOTAL     PIC S9(7)V99 COMP-3.
"""

TWO = """
       01  REC.
           05  N1        PIC S9(4) COMP.
           05  A OCCURS 1 TO 4 TIMES DEPENDING ON N1.
               10  A-VAL PIC S9(9) COMP.
           05  MIDDLE    PIC X(5).
           05  N2        PIC S9(3) COMP-3.
           05  B OCCURS 0 TO 3 TIMES DEPENDING ON N2.
               10  B-KEY PIC X(3).
               10  B-VAL PIC 9(4).
           05  LAST-ONE  PIC 9(6).
"""

TRAILING = """
       01  REC.
           05  ID        PIC 9(4).
           05  CNT       PIC 9(2).
           05  ITEMS OCCURS 0 TO 12 TIMES DEPENDING ON CNT.
               10  V     PIC S9(4) COMP.
"""

REDEFINES_BEHIND = """
       01  REC.
           05  CNT       PIC 9(1).
           05  ITEMS OCCURS 1 TO 3 TIMES DEPENDING ON CNT.
               10  CODE  PIC X(2).
           05  AS-TEXT   PIC X(8).
           05  AS-NUM REDEFINES AS-TEXT.
               10  HI    PIC 9(4).
               10  LO    PIC 9(4).
           05  AFTER     PIC S9(4) COMP.
"""

SEGMENTS = """
       01  REC.
           05  SEG-ID        PIC X(1).
           05  ORDERS.
               10  ORD-NO    PIC 9(5).
               10  ORD-CNT   PIC 9(1).
               10  ORD-LINES OCCURS 0 TO 4 TIMES DEPENDING ON ORD-CNT.
                   15  SKU   PIC X(4).
                   15  QTY   PIC S9(4) COMP.
               10  ORD-NOTE  PIC X(6).
           05  NOTES REDEFINES ORDERS.
               10  NOTE-TEXT PIC X(20).
"""
SEGMENT_OPTIONS = dict(segment_field="SEG-ID",
                       redefine_segment_id_map="ORDERS => O",
                       redefine_segment_id_map_1="NOTES => N")

NUMERIC_ELEMENT = """
       01  REC.
           05  CNT       PIC S9(4) COMP.
           05  ITEMS OCCURS 0 TO 6 TIMES DEPENDING ON CNT.
               10  P     PIC S9(4) COMP.
               10  Q     PIC S9(7)V99 COMP-3.
           05  TAIL      PIC 9(3).
"""

WIDE_ELEMENT = """
       01  REC.
           05  CNT       PIC 9(1).
           05  ITEMS OCCURS 0 TO 3 TIMES DEPENDING ON CNT.
               10  BIG   PIC S9(20)V99 COMP-3.
               10  NAME  PIC X(3).
           05  TAIL      PIC X(4).
"""

NESTED = """
       01  REC.
           05  OUTER-CNT PIC 9(1).
           05  INNER-CNT PIC 9(1).
           05  OUTER OCCURS 1 TO 2 TIMES DEPENDING ON OUTER-CNT.
               10  INNER OCCURS 0 TO 3 TIMES DEPENDING ON INNER-CNT.
                   15  V PIC X(2).
           05  TAIL      PIC X(3).
"""

CROSS_REDEFINE = """
       01  REC.
           05  SEG-ID        PIC X(1).
           05  ORDERS.
               10  ORD-CNT   PIC 9(1).
               10  ORD-PAD   PIC X(19).
           05  NOTES REDEFINES ORDERS.
               10  NOTE-LINES OCCURS 1 TO 4 TIMES DEPENDING ON ORD-CNT.
                   15  NOTE  PIC X(4).
               10  NOTE-END  PIC X(4).
"""


def name(rnd, width):
    return "".join(rnd.choice("ABCDEFGH") for _ in range(
        rnd.randint(0, width)))


def mid_rows(rnd, n, counts=None):
    rows = []
    for i in range(n):
        c = rnd.randint(0, 5) if counts is None else counts[i % len(counts)]
        items = [(rnd.randint(-999, 999),
                  D(rnd.randint(-9999999, 9999999)) / 100, name(rnd, 6))
                 for _ in range(c)]
        rows.append([(i - 3, c, items, "T%07d" % i, D(i * 7) / 100)])
    return rows


def two_rows(rnd, n):
    rows = []
    for i in range(n):
        a = [(rnd.randint(-10 ** 8, 10 ** 8),)
             for _ in range(rnd.randint(1, 4))]
        b = [(name(rnd, 3), rnd.randint(0, 9999))
             for _ in range(rnd.randint(0, 3))]
        rows.append([(len(a), a, name(rnd, 5), len(b), b, i)])
    return rows


def trailing_rows(rnd, n):
    return [[(i, c, [(rnd.randint(-9999, 9999),) for _ in range(c)])]
            for i, c in ((i, rnd.randint(0, 12)) for i in range(n))]


def redefines_rows(rnd, n):
    return [[(c, [(name(rnd, 2),) for _ in range(c)],
              "%04d%04d" % (rnd.randint(0, 9999), rnd.randint(0, 9999)),
              None, rnd.randint(-9999, 9999))]
            for c in (rnd.randint(1, 3) for _ in range(n))]


def numeric_rows(rnd, n):
    return [[(c, [(rnd.randint(-9999, 9999),
                   D(rnd.randint(-10 ** 8, 10 ** 8)) / 100)
                  for _ in range(c)], i % 1000)]
            for i, c in ((i, rnd.randint(0, 6)) for i in range(n))]


def wide_rows(rnd, n):
    return [[(c, [(D(rnd.randint(-10 ** 21, 10 ** 21)) / 100, name(rnd, 3))
                  for _ in range(c)], name(rnd, 4))]
            for c in (rnd.randint(0, 3) for _ in range(n))]


def segment_rows(rnd, n):
    rows = []
    for i in range(n):
        if rnd.random() < 0.6:
            c = rnd.randint(0, 4)
            lines = [(name(rnd, 4), rnd.randint(-999, 999))
                     for _ in range(c)]
            rows.append([("O", (i, c, lines, name(rnd, 6)), None)])
        else:
            rows.append([("N", None, (name(rnd, 20),))])
    return rows


def rdw(bodies) -> bytes:
    return b"".join(RecordEncoder.rdw_header(len(b)) + b for b in bodies)


def encode(copybook, rows):
    enc = RecordEncoder(copybook, variable_size_occurs=True)
    return [enc.encode_record(r, pad=False) for r in rows]


def write(tmp_path, bodies, name_="odo.bin") -> str:
    path = tmp_path / name_
    path.write_bytes(rdw(bodies))
    return str(path)


def read(path, copybook, backend, **options):
    options.setdefault("is_record_sequence", "true")
    options.setdefault("schema_retention_policy", "collapse_root")
    return read_cobol(path, copybook_contents=copybook,
                      variable_size_occurs="true", backend=backend,
                      **options)


def stage_counts(metrics: dict) -> dict:
    return metrics.get("device", {}).get("stage_n", {})


def assert_batched(path, copybook, backend, rows, expanded=None,
                   **options):
    """The batched read of `path` equals the record walk's, took the
    batched route for every record (`expanded` of them under a plan
    with regions: all, but for the other segments' of a multisegment
    file), and on a device backend launched."""
    expanded = rows if expanded is None else expanded
    ref = read(path, copybook, "host", **options)
    data = read(path, copybook, backend, **options)
    table = data.to_arrow()
    expected = ref.to_arrow()
    assert table.schema.equals(expected.schema)
    assert table.num_rows == rows
    for column in table.column_names:
        assert table[column].equals(expected[column]), column
    assert data.to_rows() == ref.to_rows()
    metrics = data.metrics.as_dict()
    odo = metrics["odo"]
    assert odo["odo_records"] == expanded
    assert odo["odo_fallback_records"] == 0
    assert odo["odo_regions"] >= 1
    if backend in columnar.DEVICE_BACKENDS:
        device = metrics["device"]
        assert sum(device["launches"].values()) >= 1
        assert device["records"] == rows
        assert device["odo_records"] == expanded
        assert device["odo_fallback_records"] == 0
    return data, metrics


# -- the layouts the batched route takes ------------------------------------

LAYOUTS = {
    "mid": (MID, mid_rows, {}),
    "two": (TWO, two_rows, {}),
    "trailing": (TRAILING, trailing_rows, {}),
    "redefines_behind": (REDEFINES_BEHIND, redefines_rows, {}),
    "numeric_element": (NUMERIC_ELEMENT, numeric_rows, {}),
    "segment_redefine": (SEGMENTS, segment_rows, SEGMENT_OPTIONS),
}


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_layout_equals_record_walk(tmp_path, layout, backend):
    copybook, make, options = LAYOUTS[layout]
    rows = make(random.Random(len(layout)), 61)
    path = write(tmp_path, encode(copybook, rows))
    expanded = sum(r[0][0] != "N" for r in rows)   # the other segment's
    _, metrics = assert_batched(path, copybook, backend, 61, expanded,
                                **options)
    assert metrics["odo"]["odo_regions"] == (2 if layout == "two" else 1)


@pytest.mark.parametrize("backend", BATCHED)
def test_shifted_bytes_are_what_the_counts_say(tmp_path, backend):
    rows = mid_rows(random.Random(3), 40)
    path = write(tmp_path, encode(MID, rows))
    _, metrics = assert_batched(path, MID, backend, 40)
    missing = sum(5 - r[0][1] for r in rows)
    assert metrics["odo"]["odo_shifted_bytes"] == missing * 12


@pytest.mark.parametrize("backend", BATCHED)
def test_counts_at_the_bounds_and_outside_them(tmp_path, backend):
    """Minimum (zero here), maximum, out of bounds and non-digit counts:
    the last two take the maximum, as the walk does, so such a record
    holds five elements' bytes."""
    rnd = random.Random(11)
    bodies = encode(MID, mid_rows(rnd, 12, counts=[0, 5, 1, 5]))
    full = encode(MID, mid_rows(rnd, 4, counts=[5]))
    for body, count_byte in zip(full, (0xF9, 0xF7, 0x40, 0x7B)):
        spoiled = bytearray(body)
        spoiled[4] = count_byte   # '9', '7', a space, '#'
        bodies.append(bytes(spoiled))
    path = write(tmp_path, bodies)
    data, _ = assert_batched(path, MID, backend, 16)
    lengths = [len(v) for v in data.to_arrow()["ITEMS"].to_pylist()]
    assert lengths == [0, 5, 1, 5] * 3 + [5, 5, 5, 5]


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("dependee", ["PIC 9(2)", "PIC S9(4) COMP",
                                      "PIC S9(3) COMP-3", "PIC 9(4) COMP"])
def test_dependee_codecs(tmp_path, backend, dependee):
    copybook = MID.replace("CNT       PIC 9(1)", "CNT       " + dependee)
    rows = mid_rows(random.Random(5), 33)
    path = write(tmp_path, encode(copybook, rows))
    assert_batched(path, copybook, backend, 33)


@pytest.mark.parametrize("backend", BATCHED)
def test_records_shorter_and_longer_than_their_walk(tmp_path, backend):
    """A record cut inside the field behind the array, inside a visible
    element, and right behind its count; and one with bytes to spare."""
    rnd = random.Random(2)
    bodies = encode(MID, mid_rows(rnd, 8, counts=[3, 0, 5, 2]))
    whole = encode(MID, mid_rows(rnd, 5, counts=[3]))
    bodies += [whole[0][:-9], whole[1][:5 + 12 + 4], whole[2][:5],
               whole[3] + b"\x40" * 9, whole[4][:5 + 36 + 3]]
    path = write(tmp_path, bodies)
    data, _ = assert_batched(path, MID, backend, 13)
    tail = data.to_arrow()["TAIL"].to_pylist()
    assert tail[8] == "T00000"                        # a cut string
    assert data.to_arrow()["TOTAL"].to_pylist()[8] is None


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_cut_padded_and_spoiled_records_equal_the_walk(tmp_path, layout,
                                                       backend):
    """Records cut at any byte, padded with noise, or with one byte
    spoiled (counts included): whatever the walk makes of them."""
    copybook, make, options = LAYOUTS[layout]
    for seed in (1, 2):
        rnd = random.Random(seed)
        bodies = []
        for body in encode(copybook, make(rnd, 40)):
            kind = rnd.random()
            if kind < 0.3:
                body = body[:rnd.randint(1, len(body))]
            elif kind < 0.4:
                body += bytes(rnd.randint(0, 255)
                              for _ in range(rnd.randint(1, 12)))
            elif kind < 0.5:
                spoiled = bytearray(body)
                spoiled[rnd.randrange(len(spoiled))] = rnd.randint(0, 255)
                body = bytes(spoiled)
            bodies.append(body)
        path = write(tmp_path, bodies, f"damaged{seed}.bin")
        expected = read(path, copybook, "host", **options).to_arrow()
        table = read(path, copybook, backend, **options).to_arrow()
        for column in table.column_names:
            assert table[column].equals(expected[column]), (seed, column)


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("rows", [1, 300])
def test_one_row_and_a_ragged_last_block(tmp_path, monkeypatch, backend,
                                         rows):
    """One record; and 300 in device blocks of 256: the last block is 44
    rows padded to the bucket."""
    monkeypatch.setattr(columnar, "DEVICE_BLOCK_BYTES", 256 * 80)
    path = write(tmp_path, encode(MID, mid_rows(random.Random(rows), rows)))
    _, metrics = assert_batched(path, MID, backend, rows)
    if backend in columnar.DEVICE_BACKENDS and rows == 300:
        assert metrics["device"]["launches"] == {"256x80": 2}


@pytest.mark.parametrize("backend", BATCHED)
def test_several_index_shards(tmp_path, backend):
    """The RDW gives every length: the file is framed by the native
    scanner and cut into index shards like any other RDW file."""
    path = write(tmp_path, encode(TWO, two_rows(random.Random(8), 500)))
    data, metrics = assert_batched(path, TWO, backend, 500,
                                   input_split_records="120")
    assert metrics["shards"] >= 4
    assert data.to_arrow()["LAST_ONE"].to_pylist() == list(range(500))


@pytest.mark.parametrize("backend", BATCHED)
def test_element_routes(tmp_path, backend):
    """Numerics only: the plane route; numerics and strings: one struct
    a list, record-major; a decimal past 18 digits: the slots, counted
    under `assemble.list.slots`."""
    rnd = random.Random(21)
    cases = [(NUMERIC_ELEMENT, numeric_rows, False),
             (MID, mid_rows, False), (WIDE_ELEMENT, wide_rows, True)]
    for k, (copybook, make, slots) in enumerate(cases):
        path = write(tmp_path, encode(copybook, make(rnd, 30)), f"r{k}.bin")
        data, _ = assert_batched(path, copybook, backend, 30)
        metrics = data.metrics.as_dict()
        passes = metrics.get("native_passes", {})
        if backend == "numpy":
            # the fused native pass builds wide decimals flat as well
            assert passes.get("struct_list") == 1
            continue
        assert passes.get("struct_list", 0) == (0 if slots else 1)
        if backend in columnar.DEVICE_BACKENDS:
            assert ("assemble.list.slots" in stage_counts(metrics)) == slots
            if not slots:
                assert passes.get("plane_list", 0) >= 2


@pytest.mark.parametrize("backend", BATCHED)
def test_projection_and_filter_over_regions(tmp_path, backend):
    rows = two_rows(random.Random(13), 80)
    path = write(tmp_path, encode(TWO, rows))
    ref = read(path, TWO, "numpy").to_arrow()
    data = read(path, TWO, backend, select="N2,B,LAST_ONE")
    table = data.to_arrow()
    for column in ("N2", "B", "LAST_ONE"):
        assert table[column].equals(ref[column]), column
    assert data.metrics.as_dict()["odo"]["odo_records"] == 80
    kept = read(path, TWO, backend, filter="LAST_ONE >= 40").to_arrow()
    assert kept["LAST_ONE"].to_pylist() == list(range(40, 80))
    assert kept["B"].equals(ref["B"].slice(40))


# -- what stays on the record walk, counted ---------------------------------

def nested_rows(rnd, n):
    rows = []
    for _ in range(n):
        outer, inner = rnd.randint(1, 2), rnd.randint(0, 3)
        rows.append([(outer, inner,
                      [([(name(rnd, 2),) for _ in range(inner)],)
                       for _ in range(outer)], name(rnd, 3))])
    return rows


def cross_rows(rnd, n):
    rows = []
    for i in range(n):
        if i % 2 == 0:
            rows.append([("O", (rnd.randint(1, 4), name(rnd, 19)), None)])
        else:
            rows.append([("N", None, ([(name(rnd, 4),)
                                       for _ in range(4)], name(rnd, 4)))])
    return rows


ROW_PATH = {
    # the inner count in the record's prefix: element rows carry it
    "nested": (NESTED, nested_rows, {}, "OUTER holds variable arrays"),
    "cross_redefine": (CROSS_REDEFINE, cross_rows,
                       dict(segment_field="SEG-ID",
                            redefine_segment_id_map="ORDERS => O",
                            redefine_segment_id_map_1="NOTES => N"),
                       "ORD_CNT"),
}


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("layout", sorted(ROW_PATH))
def test_layouts_left_to_the_record_walk(tmp_path, layout, backend):
    """What the batched route leaves to the walk, counted; a variable
    array of variable arrays ("nested") now goes by element rows."""
    copybook, make, options, why = ROW_PATH[layout]
    rows = make(random.Random(4), 25)
    path = write(tmp_path, encode(copybook, rows))
    ref = read(path, copybook, "host", **options).to_arrow()
    data = read(path, copybook, backend, **options)
    assert data.to_arrow().equals(ref)
    metrics = data.metrics.as_dict()
    from cobrix_tpu.explain import explain
    plan = explain(copybook_contents=copybook, is_record_sequence="true",
                   variable_size_occurs="true", **options).plan
    assert why in plan["variable_occurs_reason"]
    if layout == "nested":
        elements = sum(r[0][0] for r in rows)
        assert plan["variable_occurs"] == "elements"
        assert metrics["odo"]["odo_nested_records"] == 25
        assert metrics["odo"]["odo_elements"] == elements
        assert metrics["odo"]["odo_records"] == elements
        assert metrics["odo"]["odo_nested_fallback_records"] == 0
        assert ("device" in metrics) == (backend in columnar.DEVICE_BACKENDS)
        return
    assert metrics["odo"] == {"odo_regions": 0, "odo_records": 0,
                              "odo_fallback_records": 25,
                              "odo_shifted_bytes": 0}
    assert "device" not in metrics      # no byte reached a device
    assert plan["variable_occurs"] == "rows"


@pytest.mark.parametrize("backend", BATCHED)
def test_file_without_rdw_is_walked(tmp_path, backend):
    """No RDW: the record's length is the walk (VarOccursRecordExtractor)."""
    bodies = encode(MID, mid_rows(random.Random(9), 20))
    path = tmp_path / "plain.bin"
    path.write_bytes(b"".join(bodies))
    ref = read(str(path), MID, "host", is_record_sequence="false")
    data = read(str(path), MID, backend, is_record_sequence="false")
    assert data.to_arrow().equals(ref.to_arrow())
    assert data.to_arrow().num_rows == 20
    metrics = data.metrics.as_dict()
    assert metrics["odo"]["odo_fallback_records"] == 20
    assert metrics["odo"]["odo_records"] == 0
    assert "device" not in metrics


def test_explain_reports_the_route_and_the_regions():
    from cobrix_tpu.explain import explain

    plan = explain(copybook_contents=TWO, is_record_sequence="true",
                   variable_size_occurs="true").plan
    assert plan["variable_occurs"] == "batched"
    assert plan["variable_regions"] == ["A[1..4]x4B@2", "B[0..3]x7B@25"]
    assert "variable_occurs_reason" not in plan
    off = explain(copybook_contents=TWO, is_record_sequence="true").plan
    assert "variable_occurs" not in off


# -- a variable array of variable arrays: element rows ----------------------

CUSTOMERS = """
       01  CUST.
           05  C-ID      PIC S9(9) COMP.
           05  C-NAME    PIC X(6).
           05  C-CNT     PIC 9(2).
           05  C-ORDERS OCCURS 0 TO 40 TIMES DEPENDING ON C-CNT.
               10  O-KEY     PIC S9(9) COMP.
               10  O-AMT     PIC S9(7)V99 COMP-3.
               10  O-CNT     PIC 9(1).
               10  O-LINES OCCURS 1 TO 7 TIMES DEPENDING ON O-CNT.
                   15  L-QTY PIC S9(4) COMP.
                   15  L-TXT PIC X(3).
               10  O-NOTE    PIC X(5).
           05  C-COMMENT PIC X(8).
"""
# C-ID 0, C-CNT 10, C-ORDERS 12: an order 10 + 7 x 5 + 5 = 50 B at most
ORDER_AT, ORDER_HEAD, LINE, ORDER_TAIL = 12, 10, 5, 5

TWO_INNER = """
       01  REC.
           05  N         PIC 9(1).
           05  GRP OCCURS 0 TO 3 TIMES DEPENDING ON N.
               10  A-CNT PIC 9(1).
               10  A OCCURS 0 TO 3 TIMES DEPENDING ON A-CNT.
                   15  A-V PIC S9(4) COMP.
               10  B-CNT PIC S9(3) COMP-3.
               10  B OCCURS 1 TO 2 TIMES DEPENDING ON B-CNT.
                   15  B-V PIC X(3).
               10  G-END PIC 9(2).
           05  TAIL      PIC X(4).
"""


def customer_rows(rnd, n, orders=None, lines=None):
    rows = []
    for i in range(n):
        c = (rnd.choice([0, 1, 2, 5, 40]) if orders is None
             else orders[i % len(orders)])
        elements = []
        for k in range(c):
            m = rnd.randint(1, 7) if lines is None else lines[k % len(lines)]
            elements.append((rnd.randint(-10 ** 8, 10 ** 8),
                             D(rnd.randint(-10 ** 8, 10 ** 8)) / 100, m,
                             [(rnd.randint(-999, 999), name(rnd, 3))
                              for _ in range(m)], name(rnd, 5)))
        rows.append([(i, name(rnd, 6), c, elements, "E%07d" % i)])
    return rows


def two_inner_rows(rnd, n):
    rows = []
    for i in range(n):
        groups = []
        for _ in range(rnd.randint(0, 3)):
            a, b = rnd.randint(0, 3), rnd.randint(1, 2)
            groups.append((a, [(rnd.randint(-999, 999),) for _ in range(a)],
                           b, [(name(rnd, 3),) for _ in range(b)],
                           rnd.randint(0, 99)))
        rows.append([(len(groups), groups, name(rnd, 4))])
    return rows


ELEMENT_LAYOUTS = {
    "customers": (CUSTOMERS, customer_rows),
    "two_inner": (TWO_INNER, two_inner_rows),
    "prefix_count": (NESTED, nested_rows),
}


def decoder_width(copybook, kind):
    parsed = parse_copybook(copybook)
    outer, _ = array_of_arrays(parsed)
    return compile_plan(parsed, variable_size_occurs=True,
                        rows_of=(kind, outer.name)).max_extent


def assert_element_rows(path, copybook, backend, rows, elements,
                        walked=0, **options):
    """The read by element rows equals the record walk's, table for table
    and row for row, with `walked` records left to the walk, counted."""
    ref = read(path, copybook, "host", **options)
    data = read(path, copybook, backend, **options)
    table, expected = data.to_arrow(), ref.to_arrow()
    assert table.schema.equals(expected.schema)
    assert table.num_rows == rows
    for column in table.column_names:
        assert table[column].equals(expected[column]), column
    assert data.to_rows() == ref.to_rows()
    metrics = data.metrics.as_dict()
    odo = metrics["odo"]
    assert odo["odo_nested_records"] == rows - walked
    assert odo["odo_elements"] == elements
    assert odo["odo_nested_fallback_records"] == walked
    if backend in columnar.DEVICE_BACKENDS:
        device = metrics["device"]
        # the two row kinds, each at its own width
        assert {shape.split("x")[1] for shape in device["launches"]} == {
            str(decoder_width(copybook, kind)) for kind in ("owner",
                                                            "element")}
        assert device["records"] == rows - walked + elements
        assert device["odo_nested_records"] == rows - walked
    return data, metrics


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("layout", sorted(ELEMENT_LAYOUTS))
def test_element_rows_equal_the_record_walk(tmp_path, layout, backend):
    """Customers of 0, 1, 2, 5 and 40 orders of 1 to 7 lines, the comment
    behind them; two variable arrays in an element, the second's count
    behind the first; the inner count in the record's prefix."""
    copybook, make = ELEMENT_LAYOUTS[layout]
    rows = make(random.Random(len(layout)), 37)
    path = write(tmp_path, encode(copybook, rows))
    assert_element_rows(path, copybook, backend, 37,
                        sum(r[0][-3 if layout == "customers" else 0]
                            for r in rows))


def spoil(body: bytes, at: int, value: bytes) -> bytes:
    return body[:at] + value + body[at + len(value):]


@pytest.mark.parametrize("backend", BATCHED)
def test_element_rows_at_the_bounds_and_outside_them(tmp_path, backend):
    """Counts outside their bounds or not digits take the maximum, as the
    walk does: a customer of 40 orders whose count reads 99 or spaces, an
    order of 7 lines whose count reads 9, 0 or a space (the first element
    of its record: nothing read before it). Such records stay whole and go
    by element rows."""
    rnd = random.Random(5)
    bodies = encode(CUSTOMERS, customer_rows(rnd, 6, orders=[0, 1, 40],
                                             lines=[1, 7]))
    full = encode(CUSTOMERS, customer_rows(rnd, 2, orders=[40],
                                           lines=[7]))
    bodies += [spoil(full[0], 10, b"\xf9\xf9"), spoil(full[1], 10,
                                                      b"\x40\x40")]
    seven = encode(CUSTOMERS, customer_rows(rnd, 3, orders=[1], lines=[7]))
    bodies += [spoil(body, ORDER_AT + ORDER_HEAD - 1, value)
               for body, value in zip(seven, (b"\xf9", b"\xf0", b"\x40"))]
    path = write(tmp_path, bodies)
    data, _ = assert_element_rows(path, CUSTOMERS, backend, 11,
                                  2 * 41 + 40 + 40 + 3)
    orders = data.to_arrow()["C_ORDERS"].to_pylist()
    assert [len(o) for o in orders] == [0, 1, 40, 0, 1, 40, 40, 40, 1, 1, 1]
    assert [len(o[0]["O_LINES"]) for o in orders[8:]] == [7, 7, 7]


@pytest.mark.parametrize("backend", BATCHED)
def test_records_element_rows_cannot_take_are_walked(tmp_path, backend):
    """A record cut inside an element it shows, one cut inside the field
    behind the array, one cut inside its prefix, and one whose second
    order's count does not decode (the walk keeps the first order's
    count): each decoded by the record walk alone, counted; and one
    with bytes to spare, which is whole."""
    rnd = random.Random(9)
    bodies = encode(CUSTOMERS, customer_rows(rnd, 5, orders=[2, 3]))
    whole = encode(CUSTOMERS, customer_rows(rnd, 5, orders=[2], lines=[3]))
    second = ORDER_AT + ORDER_HEAD + 3 * LINE + ORDER_TAIL
    bodies += [whole[0][:ORDER_AT + 20], whole[1][:-3], whole[2][:8],
               spoil(whole[3], second + ORDER_HEAD - 1, b"\x40"),
               whole[4] + b"\x40" * 6]
    path = write(tmp_path, bodies)
    data, metrics = assert_element_rows(
        path, CUSTOMERS, backend, 10,
        sum(r[0][2] for r in customer_rows(random.Random(9), 5,
                                           orders=[2, 3])) + 2, walked=4)
    assert metrics["odo"]["odo_fallback_records"] == 0
    assert data.to_arrow()["C_COMMENT"].to_pylist()[9] == "E0000004"


@pytest.mark.parametrize("backend", BATCHED)
@pytest.mark.parametrize("kind", ["cut", "padded", "spoiled"])
def test_damaged_records_by_element_rows_equal_the_walk(tmp_path, kind,
                                                         backend):
    """Records cut at any byte, padded with noise, or with one byte
    spoiled (counts included): whatever the walk makes of them."""
    rnd = random.Random(kind)
    bodies = []
    for body in encode(CUSTOMERS, customer_rows(rnd, 40)):
        if rnd.random() < 0.5:
            if kind == "cut":
                body = body[:rnd.randint(1, len(body))]
            elif kind == "padded":
                body += bytes(rnd.randint(0, 255)
                              for _ in range(rnd.randint(1, 12)))
            else:
                body = spoil(body, rnd.randrange(len(body)),
                             bytes([rnd.randint(0, 255)]))
        bodies.append(body)
    path = write(tmp_path, bodies)
    expected = read(path, CUSTOMERS, "host").to_arrow()
    table = read(path, CUSTOMERS, backend).to_arrow()
    for column in table.column_names:
        assert table[column].equals(expected[column]), column


@pytest.mark.parametrize("backend", BATCHED)
def test_element_rows_with_generated_columns_and_shards(tmp_path, backend):
    """Record_Id and the file name beside the columns, the file cut into
    index shards, the schema kept under its root, a walked record among
    them: in file order, as the walk numbers them."""
    rnd = random.Random(3)
    bodies = encode(CUSTOMERS, customer_rows(rnd, 120))
    bodies[50] = bodies[50][:ORDER_AT + 3]
    path = write(tmp_path, bodies)
    options = dict(generate_record_id="true", input_split_records="25",
                   with_input_file_name_col="F",
                   schema_retention_policy="keep_original")
    _, metrics = assert_element_rows(
        path, CUSTOMERS, backend, 120,
        sum(r[0][2] for i, r in enumerate(
            customer_rows(random.Random(3), 120)) if i != 50),
        walked=1, **options)
    assert metrics["shards"] >= 4


@pytest.mark.parametrize("backend", BATCHED)
def test_element_rows_counters_and_stages(tmp_path, backend):
    rows = customer_rows(random.Random(17), 30)
    path = write(tmp_path, encode(CUSTOMERS, rows))
    data = read(path, CUSTOMERS, backend)
    data.to_arrow()
    metrics = data.metrics.as_dict()
    assert set(metrics["odo"]) == {
        "odo_regions", "odo_records", "odo_fallback_records",
        "odo_shifted_bytes", "odo_nested_records", "odo_elements",
        "odo_nested_fallback_records"}
    elements = sum(r[0][2] for r in rows)
    assert (metrics["odo"]["odo_nested_records"],
            metrics["odo"]["odo_elements"],
            metrics["odo"]["odo_nested_fallback_records"]) == (
                30, elements, 0)
    # the elements' own lines through the single-level expansion
    assert metrics["odo"]["odo_records"] == elements
    assert metrics["native_passes"]["struct_list"] >= 1
    stats = data.metrics.device_stats
    for stage in ("frame.elements", "assemble.list.nested", "expand"):
        assert stats.stage_n[stage] >= 1, stage
    assert "assemble.list.slots" not in stats.stage_n
    if backend in columnar.DEVICE_BACKENDS:
        device = metrics["device"]
        for key, value in metrics["odo"].items():
            assert device[key] == value
        assert "frame.elements" in device["stage_s"]


def test_explain_reports_element_rows():
    from cobrix_tpu.explain import explain

    plan = explain(copybook_contents=CUSTOMERS, is_record_sequence="true",
                   variable_size_occurs="true").plan
    assert plan["variable_occurs"] == "elements"
    assert plan["variable_occurs_reason"] == (
        "C_ORDERS holds variable arrays: an element is a row of its own")
    assert plan["variable_regions"] == ["C_ORDERS[0..40]x50B@12",
                                        "O_LINES[1..7]x5B@10 in C_ORDERS"]


@pytest.mark.parametrize("copybook,why,options", [
    # a third level
    ("""
       01  REC.
           05  N1  PIC 9(1).
           05  A OCCURS 0 TO 2 TIMES DEPENDING ON N1.
               10  N2  PIC 9(1).
               10  B OCCURS 0 TO 2 TIMES DEPENDING ON N2.
                   15  N3  PIC 9(1).
                   15  C OCCURS 0 TO 2 TIMES DEPENDING ON N3.
                       20  V PIC X(1).
           05  TAIL PIC X(2).
     """, "C is a variable array inside another array", {}),
    # the outer array under a REDEFINES
    ("""
       01  REC.
           05  N1  PIC 9(1).
           05  PLAIN PIC X(20).
           05  OVER REDEFINES PLAIN.
               10  A OCCURS 0 TO 2 TIMES DEPENDING ON N1.
                   15  N2  PIC 9(1).
                   15  B OCCURS 0 TO 3 TIMES DEPENDING ON N2.
                       20  V PIC X(3).
     """, "A is a variable array under a REDEFINES", {}),
    # another variable array beside it
    (CUSTOMERS.replace("           05  C-COMMENT PIC X(8).",
                       "           05  T OCCURS 0 TO 2 TIMES "
                       "DEPENDING ON C-CNT.\n"
                       "               10  T-V PIC X(1)."),
     "T is a variable array beside C_ORDERS", {}),
    # no RDW
    (CUSTOMERS, "without RDW headers", dict(is_record_sequence="false")),
])
def test_arrays_of_arrays_the_route_declines(copybook, why, options):
    from cobrix_tpu.explain import explain

    options.setdefault("is_record_sequence", "true")
    plan = explain(copybook_contents=copybook, variable_size_occurs="true",
                   **options).plan
    assert plan["variable_occurs"] == "rows"
    assert why in plan["variable_occurs_reason"]


@pytest.mark.parametrize("kind,size,columns", [
    ("owner", 12 + 8, ["C_ID", "C_NAME", "C_CNT", "C_COMMENT"]),
    ("element", 50, ["O_KEY", "O_AMT", "O_CNT"] + ["L_QTY", "L_TXT"] * 7
     + ["O_NOTE"]),
])
def test_plans_of_the_two_row_kinds(kind, size, columns):
    plan = compile_plan(parse_copybook(CUSTOMERS), variable_size_occurs=True,
                        rows_of=(kind, "C_ORDERS"))
    assert plan.record_size == plan.max_extent == size
    assert [c.name for c in plan.columns] == columns
    assert plan.row_path_reason is None
    if kind == "owner":
        assert plan.regions == ()
        assert plan.columns[-1].offset == 12      # moved behind the prefix
        return
    (region,) = plan.regions
    assert (region.name, region.start, region.depend_offset,
            region.element_size, region.max_size) == ("O_LINES", 10, 9, 5, 7)
    # the inner count in the record's prefix: the element row carries it
    nested = compile_plan(parse_copybook(NESTED), variable_size_occurs=True,
                          rows_of=("element", "OUTER"))
    assert [c.name for c in nested.columns[:1]] == ["INNER_CNT"]
    assert nested.regions[0].depend_offset == 1
    assert nested.regions[0].start == 2


# -- the plan's regions ------------------------------------------------------

def plan_of(copybook_text, active=None, **options):
    copybook = parse_copybook(copybook_text, **options)
    return compile_plan(copybook, active, variable_size_occurs=True)


def test_regions_of_the_plan():
    plan = plan_of(TWO)
    first, second = plan.regions
    assert (first.name, first.start, first.element_size, first.min_size,
            first.max_size, first.end, first.max_shift) == (
                "A", 2, 4, 1, 4, 18, 12)
    assert (second.name, second.start, second.scope_end) == ("B", 25, None)
    # the second dependee sits at its static offset once A is laid out
    assert plan.columns[second.depend_col].offset == 23
    assert plan.row_path_reason is None
    # without the option the plan has no region
    copybook = parse_copybook(TWO)
    assert compile_plan(copybook).regions == ()
    trailing = plan_of(TRAILING)
    assert [r.end for r in trailing.regions] == [trailing.max_extent]


def test_region_in_a_segment_redefine_is_that_plans_alone():
    options = dict(segment_redefines=["ORDERS", "NOTES"])
    orders = plan_of(SEGMENTS, "ORDERS", **options)
    (region,) = orders.regions
    assert region.name == "ORD_LINES"
    assert region.scope_end == 1 + 36      # the redefine keeps its size
    assert plan_of(SEGMENTS, "NOTES", **options).regions == ()
    # every redefine compiled over the same bytes: no region moves them
    assert plan_of(SEGMENTS, None, **options).regions == ()


@pytest.mark.parametrize("copybook,why,options", [
    (NESTED, "inside another array", {}),
    # a string dependee counts through its handlers: the walk's business
    (MID.replace("CNT       PIC 9(1)", "CNT       PIC X(1)"),
     "not an integral number",
     dict(occurs_mappings={"ITEMS": {"A": 1, "B": 2}})),
    ("""
       01  REC.
           05  CNT       PIC 9(1).
           05  PLAIN     PIC X(12).
           05  OVER REDEFINES PLAIN.
               10  ITEMS OCCURS 0 TO 5 TIMES DEPENDING ON CNT.
                   15  QTY PIC S9(4) COMP.
           05  TAIL      PIC X(2).
     """, "under a REDEFINES", {}),
    ("""
       01  REC.
           05  GRP OCCURS 2 TIMES.
               10  CNT   PIC 9(1).
           05  ITEMS OCCURS 0 TO 5 TIMES DEPENDING ON CNT.
               10  QTY   PIC S9(4) COMP.
           05  TAIL      PIC X(2).
     """, "is inside an array", {}),
])
def test_layouts_the_plan_declines(copybook, why, options):
    plan = plan_of(copybook, **options)
    assert plan.regions == ()
    assert why in plan.row_path_reason


# -- the expansion alone -----------------------------------------------------

def loop_expand(rows, lengths, regions):
    """The expansion as a Python loop over rows and regions."""
    out = np.zeros_like(rows)
    new_lengths, all_counts = [], []
    for i, row in enumerate(rows):
        row = bytearray(row.tobytes())
        length = int(lengths[i])
        counts = []
        for r in regions:
            raw = bytes(row[r.depend_offset:r.depend_offset + r.depend_width])
            if r.depend_kind == "binary":
                value = int.from_bytes(raw, "big", signed=r.signed)
            else:
                digits = [b - 0xF0 for b in raw]
                value = (int("".join(map(str, digits)))
                         if all(0 <= d <= 9 for d in digits) else None)
            count = (value if value is not None
                     and r.min_size <= value <= r.max_size else r.max_size)
            counts.append(count)
            shift = (r.max_size - count) * r.element_size
            bound = len(row) if r.scope_end is None else r.scope_end
            compact_end = r.start + count * r.element_size
            moved = row[compact_end:bound - shift]
            row[r.end:bound] = moved[:bound - r.end]
            if length >= compact_end:
                length = (length + shift if r.scope_end is None
                          else min(length + shift, r.scope_end)
                          if length < r.scope_end else length)
        out[i] = np.frombuffer(bytes(row), dtype=np.uint8)
        new_lengths.append(min(length, len(row)))
        all_counts.append(counts)
    return out, np.asarray(new_lengths), np.asarray(all_counts)


EXPAND_REGIONS = (
    VariableRegion(name="A", depend_col=0, depend_offset=0, depend_width=2,
                   depend_kind="binary", signed=True, big_endian=True,
                   start=2, element_size=3, min_size=0, max_size=6),
    VariableRegion(name="B", depend_col=1, depend_offset=22, depend_width=1,
                   depend_kind="display_ebcdic", signed=False,
                   big_endian=True, start=23, element_size=5,
                   min_size=1, max_size=3, scope_end=44),
)


@pytest.mark.parametrize("module", ["numpy", "jax.numpy"])
def test_expansion_against_a_loop_over_rows(module):
    if module == "numpy":
        xp, kernels = np, batch_np
    else:
        import jax.numpy as xp
        from cobrix_tpu.ops import batch_jax as kernels
        kernels.ensure_x64()
    rng = np.random.default_rng(6)
    n, extent = 97, 50
    rows = rng.integers(1, 255, size=(n, extent), dtype=np.uint8)
    first = rng.integers(-1, 9, size=n)          # some out of bounds
    rows[:, 0], rows[:, 1] = (first >> 8) & 0xFF, first & 0xFF
    lengths = rng.integers(0, extent + 1, size=n)
    lengths[:40] = extent
    # the second count lies behind the first array: write it where the
    # compact record has it, at times as a non-digit
    second = rng.integers(0, 5, size=n)
    clamped = np.where((first >= 0) & (first <= 6), first, 6)
    at = 2 + clamped * 3 + 2
    rows[np.arange(n), at] = np.where(second == 4, 0x40, 0xF0 + second)
    want_rows, want_lengths, want_counts = loop_expand(
        rows, lengths, EXPAND_REGIONS)
    got_rows, got_counts = expand.expand_rows(
        xp, kernels, xp.asarray(rows), EXPAND_REGIONS)
    got_lengths, shifted = expand.expanded_lengths(
        xp, xp.asarray(lengths), got_counts, EXPAND_REGIONS, extent)
    got_rows, got_counts = np.asarray(got_rows), np.asarray(got_counts)
    np.testing.assert_array_equal(got_counts, want_counts)
    np.testing.assert_array_equal(np.asarray(got_lengths), want_lengths)
    assert got_rows.shape == rows.shape
    # every byte the plan may read: before, inside (the visible
    # elements) and behind each region
    visible = np.ones(rows.shape, dtype=bool)
    for k, r in enumerate(EXPAND_REGIONS):
        for i in range(n):
            visible[i, r.start + want_counts[i, k] * r.element_size:
                    r.end] = False
    np.testing.assert_array_equal(got_rows[visible], want_rows[visible])
    np.testing.assert_array_equal(
        np.asarray(shifted),
        ((6 - want_counts[:, 0]) * 3 + (3 - want_counts[:, 1]) * 5))


# -- stage, scope and counter names -----------------------------------------

@pytest.mark.parametrize("backend", BATCHED)
def test_stage_and_counters_in_the_metrics(tmp_path, backend):
    rows = mid_rows(random.Random(17), 50)
    path = write(tmp_path, encode(MID, rows))
    data = read(path, MID, backend)
    data.to_arrow()
    metrics = data.metrics.as_dict()
    assert set(metrics["odo"]) == {"odo_regions", "odo_records",
                                   "odo_fallback_records",
                                   "odo_shifted_bytes"}
    assert metrics["native_passes"]["struct_list"] == 1
    stats = data.metrics.device_stats
    assert stats.stage_n["expand"] >= 1 and stats.stage_s["expand"] >= 0
    if backend in columnar.DEVICE_BACKENDS:
        device = metrics["device"]
        for key, value in metrics["odo"].items():
            assert device[key] == value
        assert "expand" in device["stage_s"]
        assert "assemble.list.slots" not in device["stage_n"]


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_device_program_holds_the_expansion_scope(backend):
    import jax

    copybook = parse_copybook(MID)
    decoder = columnar.ColumnarDecoder(copybook, backend=backend,
                                       variable_size_occurs=True)
    assert len(decoder.regions) == 1
    fn = decoder.build_jax_decode_fn()
    text = jax.jit(fn).lower(jax.ShapeDtypeStruct(
        (256, decoder.plan.max_extent), np.uint8)).as_text(debug_info=True)
    assert "cobrix.expand" in text
    if backend == "pallas":
        assert text.index("cobrix.expand") < text.index("cobrix.planes")
    # no gather moves the bytes: static slices and selects
    import jax.numpy as jnp
    from cobrix_tpu.ops import batch_jax
    alone = jax.jit(lambda x: expand.expand_rows(
        jnp, batch_jax, x, decoder.regions)).lower(jax.ShapeDtypeStruct(
            (256, decoder.plan.max_extent), np.uint8)).as_text()
    assert "select" in alone
    assert not [line for line in alone.splitlines() if "gather" in line
                and "ui8>" in line.split("->")[-1]]
    # a decoder of the same copybook without the option expands nothing
    static = columnar.ColumnarDecoder(copybook, backend=backend)
    assert static.regions == ()
    plain = jax.jit(static.build_jax_decode_fn()).lower(
        jax.ShapeDtypeStruct((256, static.plan.max_extent),
                             np.uint8)).as_text(debug_info=True)
    assert "cobrix.expand" not in plain
