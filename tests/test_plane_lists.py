"""OCCURS lists built from a device backend's decoded planes
(arrow_out._plane_flat_values): the group matrix's own rows, in one
record-major pass. Every case reads one small file with a device backend
(on the CPU here: XLA's, and the Pallas interpreter) and with the host
kernels, and holds the two tables to each other logically
(`Table.equals(check_metadata=True)`) and physically (the lists' values
hold the visible rows' slots and nothing else; the table's bytes differ
by no more than validity bitmaps)."""
import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from cobrix_tpu import read_cobol
from cobrix_tpu.reader import columnar
from cobrix_tpu.testing.generators import (
    EXP3_COPYBOOK, _rdw, ebcdic_encode, encode_comp3_unsigned,
    encode_comp_be, encode_strings_column, generate_exp3)

N = 40          # records of a fixed-length case

EXP3_OPTIONS = dict(
    is_record_sequence="true", segment_field="SEGMENT-ID",
    redefine_segment_id_map="STATIC-DETAILS => C",
    redefine_segment_id_map_1="CONTACTS => P",
    copybook_contents=EXP3_COPYBOOK)


def comp(rng, rows: int, slots: int) -> np.ndarray:
    """[rows, slots * 2] bytes: `slots` PIC 9(4) COMP values a record."""
    values = rng.integers(0, 9999, size=rows * slots)
    return encode_comp_be(values, 2).reshape(rows, -1)


def comp3(rng, rows: int, slots: int, digits: int) -> np.ndarray:
    """[rows, slots * width] bytes of unsigned COMP-3 values."""
    values = rng.integers(0, 10 ** digits - 1, size=rows * slots)
    return encode_comp3_unsigned(values, digits).reshape(rows, -1)


def interleaved(a: np.ndarray, wa: int, b: np.ndarray, wb: int,
                slots: int) -> np.ndarray:
    """Element k of a group OCCURS: field k of `a`, then field k of `b`."""
    parts = []
    for k in range(slots):
        parts += [a[:, k * wa:(k + 1) * wa], b[:, k * wb:(k + 1) * wb]]
    return np.concatenate(parts, axis=1)


def fixed(copybook: str, *columns: np.ndarray) -> tuple:
    return (np.concatenate(columns, axis=1).tobytes(),
            dict(copybook_contents=copybook))


def rdw_records(bodies) -> bytes:
    return b"".join(_rdw(len(b)) + b for b in bodies)


def exp3(rng):
    seed = int(rng.integers(1, 1 << 30))
    return bytes(generate_exp3(300, seed=seed)), EXP3_OPTIONS


def primitive(rng):
    # the group matrix is exactly the leaf's slots: a zero-copy slice
    return fixed("""
       01 R.
          05 NAME PIC X(4).
          05 A OCCURS 5 PIC 9(4) COMP.
    """, encode_strings_column(["ab"] * N, 4), comp(rng, N, 5))


def group_beside_scalar(rng):
    # exp3's [n, 2001] case: X's slots sit at 1..6 of the matrix that
    # also holds ID; Y has a group of its own
    return fixed("""
       01 R.
          05 ID PIC 9(4) COMP.
          05 G OCCURS 6.
             10 X PIC 9(4) COMP.
             10 Y PIC 9(5) COMP-3.
    """, comp(rng, N, 1),
        interleaved(comp(rng, N, 6), 2, comp3(rng, N, 6, 5), 3, 6))


def group_of_one_kernel_group(rng):
    # X and Y share one matrix with ID: each leaf's slots are every
    # second column of it
    return fixed("""
       01 R.
          05 ID PIC 9(4) COMP.
          05 G OCCURS 6.
             10 X PIC 9(4) COMP.
             10 Y PIC 9(4) COMP.
    """, comp(rng, N, 1), comp(rng, N, 12))


def depending_on(rng):
    counts = rng.integers(0, 6, size=N)
    return fixed("""
       01 R.
          05 CNT PIC 9(1).
          05 A OCCURS 0 TO 5 TIMES DEPENDING ON CNT PIC 9(5) COMP-3.
    """, (0xF0 + counts).astype(np.uint8)[:, None], comp3(rng, N, 5, 5))


def decimal(rng):
    return fixed("""
       01 R.
          05 D OCCURS 4 PIC S9(5)V99 COMP-3.
    """, comp3(rng, N, 4, 7))


def invalid_nibbles(rng):
    body = comp3(rng, N, 4, 5)
    body[3, 0:3] = 0xFF
    body[7, 6:9] = 0xAB
    body[8, :] = 0xFF
    return fixed("""
       01 R.
          05 B OCCURS 4 PIC 9(5) COMP-3.
    """, body)


def masked_depending_on(rng):
    # a DEPENDING ON list under a segment redefine: its rows cannot be
    # dropped, so hidden rows are nulled in place
    bodies = []
    for i in range(N):
        if i % 3 == 0:
            bodies.append(
                ebcdic_encode("C", 1)
                + bytes([0xF0 + int(rng.integers(0, 5))])
                + comp3(rng, 1, 4, 5).tobytes())
        else:
            bodies.append(ebcdic_encode("P", 1)
                          + ebcdic_encode("hello world", 13))
    return rdw_records(bodies), dict(
        copybook_contents="""
       01 R.
          05 SEG PIC X(1).
          05 COMPANY.
             10 CNT PIC 9(1).
             10 A OCCURS 0 TO 4 TIMES DEPENDING ON CNT PIC 9(5) COMP-3.
          05 PERSON REDEFINES COMPANY.
             10 NAME PIC X(13).
    """, is_record_sequence="true", segment_field="SEG",
        redefine_segment_id_map="COMPANY => C",
        redefine_segment_id_map_1="PERSON => P")


def truncated_tail(rng):
    bodies = []
    for i in range(N):
        body = ebcdic_encode("ab", 2) + comp(rng, 1, 4).tobytes()
        bodies.append(body[:7] if i % 7 == 3 else body)
    return rdw_records(bodies), dict(
        copybook_contents="""
       01 R.
          05 NAME PIC X(2).
          05 A OCCURS 4 PIC 9(4) COMP.
    """, is_record_sequence="true")


def string_element(rng):
    return fixed("""
       01 R.
          05 S OCCURS 3 PIC X(4).
          05 A OCCURS 3 PIC 9(4) COMP.
    """, encode_strings_column(["abcdefghijkl"] * N, 12), comp(rng, N, 3))


# name -> (input maker, leaves served by the plane route per batch,
#          whether the per-slot fallback runs, rows per device block)
CASES = {
    "exp3_masked_group": (exp3, 2, False, None),
    "exp3_several_blocks": (exp3, 2, False, 64),
    "primitive_zero_copy": (primitive, 1, False, None),
    "group_beside_scalar": (group_beside_scalar, 2, False, None),
    "group_of_one_kernel_group": (group_of_one_kernel_group, 2, False, None),
    "depending_on": (depending_on, 1, False, None),
    "static_scale_decimal": (decimal, 1, False, None),
    "invalid_bcd_nibbles": (invalid_nibbles, 1, False, None),
    "masked_depending_on": (masked_depending_on, 1, False, None),
    "truncated_visible_tail": (truncated_tail, 0, True, None),
    "string_element": (string_element, 1, True, None),
}


def list_arrays(arr, path=""):
    """(path, ListArray) of every list in `arr`, the nested ones too."""
    if pa.types.is_struct(arr.type):
        for i in range(arr.type.num_fields):
            name = arr.type.field(i).name
            yield from list_arrays(arr.field(i), f"{path}/{name}")
    elif pa.types.is_list(arr.type):
        yield path, arr
        yield from list_arrays(arr.values, path + "[]")


def bitmap_room(arr) -> int:
    """Bytes that validity bitmaps of `arr` and its children can take."""
    room = (len(arr) + 7) // 8
    if pa.types.is_struct(arr.type):
        room += sum(bitmap_room(arr.field(i))
                    for i in range(arr.type.num_fields))
    elif pa.types.is_list(arr.type):
        room += bitmap_room(arr.values)
    return room


def table_lists(table):
    found = []
    for name in table.schema.names:
        column = table.column(name).combine_chunks()
        found += list(list_arrays(column, name))
    return found


@pytest.mark.parametrize("backend", ["pallas", "jax"])
@pytest.mark.parametrize("case", list(CASES))
def test_device_lists_equal_the_host_kernels(case, backend, tmp_path,
                                             monkeypatch):
    make, plane_leaves, slots_fallback, block_rows = CASES[case]
    data, options = make(np.random.default_rng(25))
    path = tmp_path / "input.bin"
    path.write_bytes(data)
    reference = read_cobol(str(path), backend="numpy", **options).to_arrow()
    if block_rows is not None:
        extent = 16064          # exp3's plan, padded to the device's tile
        monkeypatch.setattr(columnar, "DEVICE_BLOCK_BYTES",
                            block_rows * extent)
    read = read_cobol(str(path), backend=backend, **options)
    table = read.to_arrow()
    metrics = read.metrics.as_dict()
    device = metrics["device"]
    if block_rows is not None:
        assert sum(device["launches"].values()) >= 2

    assert table.equals(reference, check_metadata=True)
    # the same physical lists: values for the rows the reference holds
    # values for, in buffers no larger than its
    lists, reference_lists = table_lists(table), table_lists(reference)
    assert [p for p, _ in lists] == [p for p, _ in reference_lists] != []
    for (where, got), (_, want) in zip(lists, reference_lists):
        assert len(got.values) == len(want.values), where
        assert got.offsets.equals(want.offsets), where
    room = sum(bitmap_room(table.column(name).combine_chunks())
               for name in table.schema.names)
    assert abs(table.nbytes - reference.nbytes) <= room

    # which route built them
    assert metrics["native_passes"].get("plane_list", 0) == plane_leaves
    assert ("assemble.list.slots" in device["stage_s"]) == slots_fallback
    assert device["stage_n"]["assemble.list"] >= 1


@pytest.mark.parametrize("backend", ["pallas", "jax"])
@pytest.mark.parametrize("planes", ["subset", "whole"])
def test_exp3_lists_hold_only_the_visible_rows(planes, backend, tmp_path,
                                               monkeypatch):
    """Two thirds of exp3's rows are 64 B 'P' records under a null
    struct: their 2,000 slots are not built. `subset`: the device
    decoded the 'C' rows alone and the list's values are its matrix,
    with no row gathered; `whole`: every row was decoded (as before the
    launches went by redefine) and the visible ones are gathered."""
    if planes == "whole":
        monkeypatch.setattr(columnar, "PARTITION_MIN_SAVED_BYTES", 1 << 30)
    taken = []
    plane_of = columnar.DecodedBatch.plane_of

    def watched(self, col, rows_mask=None):
        plane, subset = plane_of(self, col, rows_mask)
        taken.append((subset, None if plane is None else plane[0].shape[0]))
        return plane, subset

    monkeypatch.setattr(columnar.DecodedBatch, "plane_of", watched)
    data, options = exp3(np.random.default_rng(7))
    path = tmp_path / "exp3.bin"
    path.write_bytes(data)
    read = read_cobol(str(path), backend=backend, **options)
    table = read.to_arrow()
    assert read.metrics.as_dict()["device"]["partitioned_batches"] == (
        planes == "subset")
    (_, strategy), = table_lists(table)
    details = (table.column("COMPANY_DETAILS").combine_chunks()
               .field("STATIC_DETAILS"))
    visible = len(details) - details.null_count
    assert 0 < visible < table.num_rows
    assert len(strategy.values) == visible * 2000
    assert strategy.values.field("NUM1").null_count == 0
    assert strategy.values.field("NUM2").null_count == 0
    # no bitmap where nothing is null
    assert strategy.values.field("NUM1").buffers()[0] is None
    hidden = np.flatnonzero(~np.asarray(details.is_valid()))
    lengths = np.diff(np.asarray(strategy.offsets))
    assert not lengths[hidden].any()
    # all 4,000 slot columns came from matrices of the visible rows
    # alone, or of every row
    assert len(taken) == 4000
    assert set(taken) == {(True, visible) if planes == "subset"
                          else (False, table.num_rows)}


@pytest.mark.parametrize("dtype", [np.int32, np.int64, bool])
@pytest.mark.parametrize("layout", [
    "row_major", "row_major_columns_sliced", "column_major",
    "column_major_rows_sliced", "column_major_columns_strided"])
def test_record_major_of_any_layout(layout, dtype):
    """A plane flat in record-major order whatever its strides: a TPU's
    column-major matrix (a block's real rows, an OCCURS leaf's every
    second column) as well as the host kernels' row-major one."""
    from cobrix_tpu.reader.arrow_out import _record_major

    base = (np.random.default_rng(4).integers(0, 1 << 30, size=(70, 37))
            .astype(dtype))
    plane = {
        "row_major": lambda: base,
        "row_major_columns_sliced": lambda: base[:, 1:],
        "column_major": lambda: np.asfortranarray(base),
        "column_major_rows_sliced": lambda: np.asfortranarray(base)[:51],
        "column_major_columns_strided":
            lambda: np.asfortranarray(base)[:, 1::2],
    }[layout]()
    flat = _record_major(plane)
    assert flat.flags.c_contiguous and flat.dtype == plane.dtype
    np.testing.assert_array_equal(flat, np.array(plane).reshape(-1))
    if layout == "row_major":
        assert np.shares_memory(flat, base)       # no copy at all


def test_a_scattered_group_keeps_its_plane():
    """A subset decode scattered back to the batch's length scatters each
    group matrix once, and its columns stay views of that matrix."""
    values = np.arange(12, dtype=np.int32).reshape(3, 4)
    valid = np.ones((3, 4), dtype=bool)
    dots = np.full((3, 4), 2, dtype=np.int8)
    subset = {col: {"values": values[:, col], "valid": valid[:, col],
                    "plane": (values, valid, col),
                    "dot_scale": dots[:, col]} for col in range(4)}
    subset[9] = {"values": values[:, 0], "valid": valid[:, 0]}   # no plane
    mask = np.array([False, True, True, False, True])
    full = columnar._scatter_outputs(subset, mask, 5)
    matrix, matrix_valid, _ = full[0]["plane"]
    assert matrix.shape == (5, 4) and matrix_valid.shape == (5, 4)
    np.testing.assert_array_equal(matrix[mask], values)
    assert not matrix[~mask].any() and not matrix_valid[~mask].any()
    for col in range(4):
        out = full[col]
        assert out["plane"][0] is matrix and out["plane"][2] == col
        assert np.shares_memory(out["values"], matrix)
        np.testing.assert_array_equal(out["values"], matrix[:, col])
        np.testing.assert_array_equal(out["valid"], matrix_valid[:, col])
        np.testing.assert_array_equal(out["dot_scale"][mask], 2)
    assert "plane" not in full[9]
    np.testing.assert_array_equal(full[9]["values"][mask], values[:, 0])
