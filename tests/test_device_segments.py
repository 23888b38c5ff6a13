"""Device launches partitioned by segment redefine
(columnar.ColumnarDecoder.decode_raw with `segment_row_masks` on a device
backend): only a segment's own rows cross the link, at the segment's own
width, and a redefine's outputs stay subset planes until a consumer asks
for them by position.

Every case reads one small input with a device backend (on the CPU here:
XLA's, and the Pallas interpreter) and with the host kernels, and holds
the two to each other by `Table.equals(check_metadata=True)` and by
`to_rows()`. The rule that decides whether a batch partitions
(`PARTITION_MIN_SAVED_BYTES`) reads the plan's widths: exp3's copybook
engages it, exp2's does not, and the small copybooks here engage it only
with the constant set to zero (the `always` fixture).
"""
import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from cobrix_tpu import parse_copybook, read_cobol
from cobrix_tpu.reader import columnar
from cobrix_tpu.reader.columnar import ColumnarDecoder
from cobrix_tpu.testing import generators as g
from cobrix_tpu.testing.generators import (
    EXP2_COPYBOOK, EXP3_COPYBOOK, _rdw, ebcdic_encode,
    encode_comp3_unsigned, encode_comp_be, generate_exp2, generate_exp3)

pytestmark = pytest.mark.jax

BACKENDS = ("jax", "pallas")
EXP3_EXTENT = 16064     # the furthest byte a 'C' row's columns read
EXP3_P_EXTENT = 60      # and a 'P' row's

COMPANIES = dict(
    is_record_sequence="true", segment_field="SEGMENT-ID",
    redefine_segment_id_map="STATIC-DETAILS => C",
    redefine_segment_id_map_1="CONTACTS => P")

# both redefines hold a PIC X(10) and a PIC 9(4) COMP: keyed by (codec,
# width, variant) alone their columns would share kernel groups. CNT is
# the dependee of a list under COMPANY; KEY belongs to no redefine
MIXED_COPYBOOK = """
       01 R.
          05 SEG    PIC X(1).
          05 KEY    PIC 9(4) COMP.
          05 COMPANY.
             10 NAME   PIC X(10).
             10 CNT    PIC 9(1).
             10 VALS   OCCURS 0 TO 4 TIMES DEPENDING ON CNT
                       PIC 9(4) COMP.
             10 AMOUNT PIC S9(7)V99 COMP-3.
             10 BIG    PIC S9(25) COMP-3.
             10 PRICE  PIC S9(3)V99.
             10 SLOTS  OCCURS 6.
                15 X   PIC 9(4) COMP.
                15 Y   PIC 9(5) COMP-3.
          05 PERSON REDEFINES COMPANY.
             10 PNAME  PIC X(10).
             10 AGE    PIC 9(4) COMP.
"""
MIXED_OPTIONS = dict(
    copybook_contents=MIXED_COPYBOOK, is_record_sequence="true",
    segment_field="SEG", redefine_segment_id_map="COMPANY => C",
    redefine_segment_id_map_1="PERSON => P")


def mixed_records(rng, kinds: str, short_every: int = 0) -> bytes:
    """One RDW record a letter of `kinds`: 'C' a whole COMPANY (every
    `short_every`-th cut short inside SLOTS), 'P' a 15 B PERSON, any
    other letter a record under no redefine."""
    out = []
    for i, kind in enumerate(kinds):
        head = (ebcdic_encode(kind, 1)
                + encode_comp_be(rng.integers(0, 9999, 1), 2).tobytes())
        if kind == "C":
            body = (
                ebcdic_encode(f"company{i}", 10)
                + bytes([0xF0 + int(rng.integers(0, 5))])
                + encode_comp_be(rng.integers(0, 9999, 4), 2).tobytes()
                + encode_comp3_unsigned(
                    rng.integers(0, 10 ** 9 - 1, 1), 9).tobytes()
                + bytes.fromhex(f"{int(rng.integers(0, 10 ** 12)):025d}c")
                + ebcdic_encode(f"{int(rng.integers(0, 99999)):05d}", 5))
            for _ in range(6):
                body += (encode_comp_be(rng.integers(0, 9999, 1),
                                        2).tobytes()
                         + encode_comp3_unsigned(
                             rng.integers(0, 99999, 1), 5).tobytes())
            if short_every and i % short_every == 0:
                body = body[:-11]
        elif kind == "P":
            body = (ebcdic_encode(f"person{i}", 10)
                    + encode_comp_be(rng.integers(0, 120, 1), 2).tobytes())
        else:
            body = ebcdic_encode("elsewhere", 9)
        out.append(_rdw(len(head + body)) + head + body)
    return b"".join(out)


def kinds_of(rng, n: int, letters: str = "CPP") -> str:
    return "".join(rng.choice(list(letters), size=n))


@pytest.fixture
def always(monkeypatch):
    """Partition whatever the widths spare."""
    monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 0)


def read_both(path, backend, **options):
    """(device read, its table, the host kernels' read, its table), the
    tables held equal, rows too."""
    device = read_cobol(str(path), backend=backend, **options)
    host = read_cobol(str(path), backend="numpy", **options)
    table, reference = device.to_arrow(), host.to_arrow()
    assert table.equals(reference, check_metadata=True)
    assert table.nbytes <= reference.nbytes + table.num_rows
    assert (read_cobol(str(path), backend=backend, **options).to_rows()
            == host.to_rows())
    return device, table, host, reference


def bucket(n: int) -> int:
    return ColumnarDecoder._bucket_size(n)


# --------------------------------------------------------------- exp3

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("block_rows", [None, 64])
def test_exp3_launches_by_redefine(backend, block_rows, tmp_path,
                                   monkeypatch):
    """exp3 engages by its widths. The bytes sent are bucket rows times
    the set's own extent, set by set; a set that fits one block is never
    merged, 'C' rows over several blocks are."""
    if block_rows is not None:
        monkeypatch.setattr(columnar, "DEVICE_BLOCK_BYTES",
                            block_rows * EXP3_EXTENT)
    path = tmp_path / "exp3.bin"
    path.write_bytes(generate_exp3(1000, seed=11))
    device, table, _, _ = read_both(
        path, backend, copybook_contents=EXP3_COPYBOOK, **COMPANIES)
    stats = device.metrics.as_dict()["device"]
    assert stats["partitioned_batches"] == 1
    assert stats["declined_batches"] == 0
    rows = stats["set_rows"]
    assert set(rows) == {"STATIC_DETAILS", "CONTACTS"}
    assert sum(rows.values()) == stats["records"] == table.num_rows
    details = (table.column("COMPANY_DETAILS").combine_chunks()
               .field("STATIC_DETAILS"))
    assert rows["STATIC_DETAILS"] == len(details) - details.null_count

    launches = stats["launches"]
    assert {int(shape.split("x")[1]) for shape in launches} == {
        EXP3_EXTENT, EXP3_P_EXTENT}
    assert stats["h2d_bytes"] == sum(
        count * int(shape.split("x")[0]) * int(shape.split("x")[1])
        for shape, count in launches.items())
    wide = {shape: count for shape, count in launches.items()
            if shape.endswith(f"x{EXP3_EXTENT}")}
    if block_rows is None:
        block = bucket(rows["STATIC_DETAILS"])
        assert wide == {f"{block}x{EXP3_EXTENT}": 1}
        assert launches[
            f"{bucket(rows['CONTACTS'])}x{EXP3_P_EXTENT}"] == 1
        assert "merge" not in stats["stage_n"]
    else:
        # 256 is the smallest bucket: the block is 256 rows
        assert wide == {f"256x{EXP3_EXTENT}":
                        -(-rows["STATIC_DETAILS"] // 256)}
        assert stats["stage_n"]["merge"] == 1
    # the two leaves of the OCCURS took the subset planes whole
    assert device.metrics.as_dict()["native_passes"]["plane_list"] == 2
    assert "assemble.list.slots" not in stats["stage_s"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("forced", [False, True])
def test_exp2_is_the_rules_other_side(backend, forced, tmp_path,
                                      monkeypatch):
    """Both exp2 redefines end at byte 64: the widths spare the link next
    to nothing and the batch stays whole. Forced to partition it reads
    the same."""
    if forced:
        monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 0)
    path = tmp_path / "exp2.bin"
    path.write_bytes(generate_exp2(900, seed=5))
    device, _, _, _ = read_both(
        path, backend, copybook_contents=EXP2_COPYBOOK, **COMPANIES)
    stats = device.metrics.as_dict()["device"]
    assert (stats["partitioned_batches"], stats["declined_batches"]) == (
        (1, 0) if forced else (0, 1))
    assert bool(stats["set_rows"]) is forced
    if not forced:
        assert list(stats["launches"]) == ["1024x64"]


def test_the_rule_reads_the_plans_widths(monkeypatch):
    """What a batch would spare the link, from the plan and the masks
    alone: exp3 some 24 KB a row, exp2 next to nothing since its
    strings come back once, a byte a code point: a 'P' row spares the
    4 B it is shorter, 4 code points and the COMP column's 5 B."""
    n = 3000
    company = np.arange(n) % 3 == 0
    masks = {"STATIC_DETAILS": company, "CONTACTS": ~company}
    exp2, exp3 = (
        ColumnarDecoder(parse_copybook(text, segment_redefines=[
            "STATIC_DETAILS", "CONTACTS"]), backend="jax")
        for text in (EXP2_COPYBOOK, EXP3_COPYBOOK))
    assert exp2._segment_sets(masks, n) is None
    # two rows in three are 'P' rows: 13 B each, 8.67 B a row (it was
    # 101 B with a uint16 slab a kernel group)
    monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 9)
    assert exp2._segment_sets(masks, n) is None
    monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 8)
    assert len(exp2._segment_sets(masks, n)) == 2
    monkeypatch.undo()
    by_name = {rs.name: rs for rs in exp3._segment_sets(masks, n)}
    assert by_name["STATIC_DETAILS"].extent == EXP3_EXTENT
    assert by_name["CONTACTS"].extent == EXP3_P_EXTENT
    assert len(by_name["STATIC_DETAILS"].rows) == 1000
    # masks that share a row are no partition; neither is no mask at all
    assert exp3._segment_sets(
        {"STATIC_DETAILS": company, "CONTACTS": np.ones(n, bool)}, n) is None
    assert exp3._segment_sets({"ELSEWHERE": company}, n) is None


# ------------------------------------------------- the small copybooks

def test_no_kernel_group_mixes_redefines():
    decoder = ColumnarDecoder(parse_copybook(
        MIXED_COPYBOOK, segment_redefines=["COMPANY", "PERSON"]))
    owners = {}
    for group in decoder.kernel_groups:
        assert {columnar._column_owner(c) for c in group.columns} == {
            group.segment}
        owners.setdefault((group.codec, group.width, group.variant),
                          set()).add(group.segment)
    # NAME and PNAME, VALS/X and AGE: one key, a group an owner
    assert sum(len(o) > 1 for o in owners.values()) >= 2
    # the dependee belongs to nobody though it sits under COMPANY
    cnt = next(c for c in decoder.plan.columns if c.name == "CNT")
    assert cnt.segment and columnar._column_owner(cnt) is None
    assert decoder.group_of_col[cnt.index].segment is None


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", [
    "mixed", "no_company_row", "rows_under_no_redefine",
    "truncated_company_rows", "one_row"])
def test_small_copybook_cases(case, backend, tmp_path, always):
    rng = np.random.default_rng(29)
    kinds, short_every = {
        "mixed": (kinds_of(rng, 90), 0),
        "no_company_row": ("P" * 40, 0),
        "rows_under_no_redefine": (kinds_of(rng, 90, "CPPX"), 0),
        "truncated_company_rows": (kinds_of(rng, 90), 3),
        "one_row": ("C", 0),
    }[case]
    path = tmp_path / "mixed.bin"
    path.write_bytes(mixed_records(rng, kinds, short_every))
    device, table, _, _ = read_both(path, backend, **MIXED_OPTIONS)
    stats = device.metrics.as_dict()["device"]
    assert stats["partitioned_batches"] == 1
    # the reader makes a mask for each redefine whose segment id it met:
    # with no 'C' record COMPANY has none, its columns stay in every
    # row's program and the one set launches the whole program
    want = {"COMPANY": kinds.count("C"), "PERSON": kinds.count("P"),
            "": len(kinds) - kinds.count("C") - kinds.count("P")}
    assert stats["set_rows"] == {k: v for k, v in want.items() if v}
    assert table.num_rows == len(kinds)


@pytest.mark.parametrize("backend", BACKENDS)
def test_decode_raw_sets_and_lazy_scatter(backend, always):
    """decode_raw itself: a redefine's columns are subset planes until
    asked for by position, a mask with no row launches nothing, the
    dependee is decoded on every row."""
    rng = np.random.default_rng(3)
    kinds = "CPPXCPPCP"
    data = mixed_records(rng, kinds)
    offsets, lengths, pos = [], [], 0
    while pos < len(data):
        size = data[pos + 2] + 256 * data[pos + 3]
        offsets.append(pos + 4)
        lengths.append(size)
        pos += 4 + size
    offsets, lengths = np.asarray(offsets), np.asarray(lengths)
    letters = np.asarray(list(kinds))
    copybook = parse_copybook(MIXED_COPYBOOK,
                              segment_redefines=["COMPANY", "PERSON"])
    masks = {"Company": letters == "C", "PERSON": letters == "P"}
    decoder = ColumnarDecoder(copybook, backend=backend)
    batch = decoder.decode_raw(data, offsets, lengths,
                               segment_row_masks=masks)
    reference = ColumnarDecoder(copybook).decode_raw(
        data, offsets, lengths, segment_row_masks=masks)
    by_name = {c.name: c for c in decoder.plan.columns if not c.slot_path}
    # subset planes of the set's own rows, the caller's mask beside them
    part = batch._out[by_name["AMOUNT"].index]["subset"]
    assert part.mask is masks["Company"]
    assert part.outputs[by_name["AMOUNT"].index]["values"].shape == (3,)
    plane, subset = batch.plane_of(by_name["AMOUNT"].index, masks["Company"])
    assert subset and plane[0].shape[0] == 3
    assert "subset" in batch._out[by_name["AMOUNT"].index]   # still lazy
    # by position: the column alone goes to its places, hidden rows
    # invalid; its group's matrices stay subsets for who wants those rows
    out = batch.column_arrays(by_name["AMOUNT"].index)
    assert out["values"].shape == (len(kinds),) and "plane" not in out
    np.testing.assert_array_equal(out["valid"], letters == "C")
    assert batch.plane_of(by_name["AMOUNT"].index, masks["Company"])[1]
    # a plane by position scatters the group's matrices, once
    slot = next(c for c in decoder.plan.columns if c.name == "X")
    plane, subset = batch.plane_of(slot.index)
    assert not subset and plane[0].shape[0] == len(kinds)
    assert batch.plane_of(slot.index, masks["Company"]) == (plane, False)
    assert batch.column_arrays(slot.index)["plane"] is plane
    # common columns and the dependee are whole from the start
    for name in ("KEY", "CNT"):
        assert "values" in batch._out[by_name[name].index], name
    upper = {name.upper(): mask for name, mask in masks.items()}
    for c in decoder.plan.columns:
        visible = upper.get(columnar._column_owner(c))
        got = batch.column_values(c.index)
        want = reference.column_values(c.index)
        for i in range(len(kinds)):
            if visible is None or visible[i]:
                assert got[i] == want[i], (c.name, i)

    # a mask without a row: no launch for it, zeros when asked
    only_p = letters != "C"
    none = {"COMPANY": np.zeros(only_p.sum(), bool),
            "PERSON": letters[only_p] == "P"}
    batch = decoder.decode_raw(data, offsets[only_p], lengths[only_p],
                               segment_row_masks=none)
    assert batch._out[by_name["AMOUNT"].index]["subset"].outputs is None
    plane, subset = batch.plane_of(by_name["AMOUNT"].index, none["COMPANY"])
    assert subset and plane[0].shape[0] == 0
    assert batch.column_values(by_name["AMOUNT"].index) == [None] * 6
    assert batch.column_values(by_name["BIG"].index) == [None] * 6
    assert batch.column_values(by_name["PRICE"].index) == [None] * 6
    ages = np.asarray(reference.column_values(by_name["AGE"].index),
                      dtype=object)[only_p]
    ages[letters[only_p] != "P"] = None
    assert batch.column_values(by_name["AGE"].index) == list(ages)

    # an empty batch never partitions
    empty = decoder.decode_raw(data, offsets[:0], lengths[:0],
                               segment_row_masks={
                                   "COMPANY": np.zeros(0, bool),
                                   "PERSON": np.zeros(0, bool)})
    assert empty.n_records == 0 and empty.to_rows() == []


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("option", ["segment_id_filter", "pushdown"])
def test_a_kept_subset_partitions(option, backend, tmp_path):
    """The masks cover the rows that were kept, whoever dropped the
    rest."""
    path = tmp_path / "exp3.bin"
    path.write_bytes(generate_exp3(300, seed=2))
    options = dict(copybook_contents=EXP3_COPYBOOK, **COMPANIES)
    if option == "segment_id_filter":
        options["segment_filter"] = "P"
    else:
        options["filter"] = "COMPANY_ID > '5'"
    device, table, _, _ = read_both(path, backend, **options)
    stats = device.metrics.as_dict()["device"]
    assert 0 < table.num_rows < 300
    assert sum(stats["set_rows"].values()) == table.num_rows
    assert stats["partitioned_batches"] == 1
    if option == "segment_id_filter":
        # the 'C' records were met and dropped: their set has no row and
        # launches nothing, no wide row crosses the link
        assert stats["set_rows"] == {"CONTACTS": table.num_rows,
                                     "STATIC_DETAILS": 0}
        assert list(stats["launches"]) == [f"256x{EXP3_P_EXTENT}"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_hierarchical_read_on_a_device_backend(backend, tmp_path, always):
    """The hierarchical walk reads every column by position: each
    redefine's planes are scattered as it comes to them."""
    seg = {f"redefine_segment_id_map:{i}": f"{name} => {sid}"
           for i, (sid, name) in enumerate(
               g.HIERARCHICAL_SEGMENT_MAP.items())}
    children = {f"segment-children:{i}": f"{parent} => {child}"
                for i, (child, parent) in enumerate(
                    g.HIERARCHICAL_PARENT_MAP.items())}
    options = dict(copybook_contents=g.HIERARCHICAL_COPYBOOK,
                   is_record_sequence="true", segment_field="SEGMENT-ID",
                   **seg, **children)
    path = tmp_path / "hierarchical.bin"
    path.write_bytes(g.generate_hierarchical(12, seed=8))
    device, _, _, _ = read_both(path, backend, **options)
    stats = device.metrics.as_dict()["device"]
    assert stats["partitioned_batches"] == 1
    assert set(stats["set_rows"]) == set(
        g.HIERARCHICAL_SEGMENT_MAP.values())
