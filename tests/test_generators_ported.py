"""End-to-end reads of the ported reference data generators
(examples-collection TestDataGen1/7/8/9/11/13a/13b/16/17 — the exp1/2/3
profiles are covered by the benchmark's cells and the golden tests).
Each test generates a dataset with the reference's record layout and
reads it back through read_cobol, pinning row counts and representative
decoded values."""
import os
import tempfile

import pytest

from cobrix_tpu import read_cobol
from cobrix_tpu.testing import generators as g


def _write(tmp, name, data: bytes) -> str:
    p = os.path.join(tmp, name)
    with open(p, "wb") as f:
        f.write(data)
    return p


def test_transactions_fixed_length_reads_back():
    data = g.generate_transactions(100, seed=7)
    assert len(data) == 100 * 45
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "tran.dat", data)
        tbl = read_cobol(
            path, copybook_contents=g.TRANSDATA_COPYBOOK,
            schema_retention_policy="collapse_root").to_arrow()
    assert tbl.num_rows == 100
    row = tbl.slice(0, 1).to_pylist()[0]
    assert row["CURRENCY"] in g._CURRENCIES
    assert row["SIGNATURE"] == "S9276511"
    assert row["WEALTH_QFY"] in (0, 1)
    assert row["AMOUNT"] is not None  # S9(9)V99 BINARY decodes


def test_transactions_with_file_header_and_footer():
    """TestDataGen13a: 10-byte header + 12-byte footer regions skipped via
    file_start_offset/file_end_offset."""
    data = g.generate_transactions(50, seed=7, file_header=10,
                                   file_footer=12)
    assert len(data) == 10 + 50 * 45 + 12
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "tran13a.dat", data)
        tbl = read_cobol(
            path, copybook_contents=g.TRANSDATA_COPYBOOK,
            file_start_offset="10", file_end_offset="12",
            schema_retention_policy="collapse_root").to_arrow()
    assert tbl.num_rows == 50
    assert tbl.column("SIGNATURE").to_pylist() == ["S9276511"] * 50


def test_non_printable_names_decode_without_crashing():
    """TestDataGen8: control-byte company names must flow through (the
    default code page maps unprintables to substitutes, never raises)."""
    data = g.generate_transactions(30, seed=7, name_pool="non_printable")
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "np.dat", data)
        tbl = read_cobol(
            path, copybook_contents=g.TRANSDATA_COPYBOOK,
            schema_retention_policy="collapse_root").to_arrow()
    assert tbl.num_rows == 30


def test_random_bytes_names_with_code_page(tmp_path):
    """TestDataGen9: random bytes in the name field, read under cp037."""
    data = g.generate_transactions(30, seed=7, name_pool="random_bytes")
    path = _write(str(tmp_path), "cp.dat", data)
    tbl = read_cobol(
        path, copybook_contents=g.TRANSDATA_COPYBOOK,
        ebcdic_code_page="cp037",
        schema_retention_policy="collapse_root").to_arrow()
    assert tbl.num_rows == 30
    assert tbl.column("COMPANY_ID").to_pylist() == ["00000000"] * 30


def test_fillers_redefines_layout():
    data = g.generate_fillers(40, seed=7)
    assert len(data) == 40 * 60
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "fill.dat", data)
        res = read_cobol(path, copybook_contents=g.FILLERS_COPYBOOK,
                         schema_retention_policy="collapse_root")
        tbl = res.to_arrow()
    assert tbl.num_rows == 40
    # FILLER groups are retained (renamed FILLER_1/FILLER_2, reference
    # renameGroupFillers), FILLER leaves inside them dropped
    assert tbl.column_names == ["COMPANY_NAME", "FILLER_1", "ADDRESS",
                                "FILLER_2", "CONTACT_PERSON", "AMOUNT"]
    row = tbl.slice(0, 1).to_pylist()[0]
    # STR1 redefines the first 5 chars of COMPANY_NAME
    assert row["COMPANY_NAME"].startswith(row["FILLER_1"]["STR1"].rstrip())


def test_custom_rdw_header_parser_reads_valid_records():
    """TestDataGen11: 5-byte custom header (validity flag + LE length);
    the custom record-header-parser seam must skip invalid records."""
    data = g.generate_custom_rdw(60, seed=7)
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "crdw.dat", data)
        tbl = read_cobol(
            path, copybook_contents=g.CUSTOM_RDW_COPYBOOK,
            is_record_sequence="true",
            record_header_parser=
            "tests.test_generators_ported.CustomFlagHeaderParser",
            segment_field="SEGMENT-ID",
            redefine_segment_id_map="STATIC-DETAILS => C",
            **{"redefine_segment_id_map:1": "CONTACTS => P"}).to_arrow()
    assert tbl.num_rows == 60
    segs = set()
    for row in tbl.column("COMPANY_DETAILS").to_pylist():
        segs.add(row["SEGMENT_ID"])
    assert segs == {"C", "P"}


def test_companies_with_file_headers_big_endian_rdw():
    """TestDataGen13b: 100-byte file header + 120-byte footer around a
    big-endian RDW multisegment stream."""
    data = g.generate_companies_with_headers(40, seed=7)
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "hdr.dat", data)
        tbl = read_cobol(
            path, copybook_contents=g.EXP2_COPYBOOK,
            is_record_sequence="true", is_rdw_big_endian="true",
            file_start_offset="100", file_end_offset="120",
            segment_field="SEGMENT-ID",
            redefine_segment_id_map="STATIC-DETAILS => C",
            **{"redefine_segment_id_map:1": "CONTACTS => P"}).to_arrow()
    assert tbl.num_rows == 40


def test_multiseg_fixed_len_three_segments():
    """TestDataGen16: fixed 64-byte records, three redefines C/P/B."""
    data = g.generate_multiseg_fixed(90, seed=7)
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "ent.dat", data)
        res = read_cobol(
            path, copybook_contents=g.ENTITY_FIXED_COPYBOOK,
            segment_field="SEGMENT-ID",
            redefine_segment_id_map="COMPANY => C",
            **{"redefine_segment_id_map:1": "PERSON => P",
               "redefine_segment_id_map:2": "PO-BOX => B"})
        tbl = res.to_arrow()
    assert tbl.num_rows == 90
    rows = tbl.column("ENTITY").to_pylist()
    seen = {r["SEGMENT_ID"] for r in rows}
    assert seen == {"C", "P", "B"}
    for r in rows:
        active = {"C": "COMPANY", "P": "PERSON", "B": "PO_BOX"}[
            r["SEGMENT_ID"]]
        assert r[active] is not None


def test_hierarchical_generator_assembles_tree():
    """TestDataGen17: 7-segment hierarchy assembled into nested rows."""
    data = g.generate_hierarchical(6, seed=7)
    opts = {"redefine_segment_id_map:%d" % i: f"{name} => {sid}"
            for i, (sid, name) in enumerate(
                g.HIERARCHICAL_SEGMENT_MAP.items())}
    child_opts = {}
    for i, (child, parent) in enumerate(g.HIERARCHICAL_PARENT_MAP.items()):
        child_opts[f"segment-children:{i}"] = f"{parent} => {child}"
    with tempfile.TemporaryDirectory() as tmp:
        path = _write(tmp, "hier.dat", data)
        res = read_cobol(
            path, copybook_contents=g.HIERARCHICAL_COPYBOOK,
            is_record_sequence="true",
            segment_field="SEGMENT-ID", **opts, **child_opts)
        tbl = res.to_arrow()
    rows = tbl.column("ENTITY").to_pylist()
    assert len(rows) == 6  # one assembled row per root company
    assert any(r["COMPANY"]["DEPT"] for r in rows)  # nested children exist


from cobrix_tpu.reader.header_parsers import RecordHeaderParser


class CustomFlagHeaderParser(RecordHeaderParser):
    """The 5-byte custom record header of TestDataGen11CustomRDW: byte 0 =
    validity flag, bytes 3-4 = little-endian payload length (the analogue
    of the reference's custom RecordHeaderParser seam)."""

    @property
    def header_length(self):
        return 5

    @property
    def is_header_defined_in_copybook(self):
        return False

    def get_record_metadata(self, header: bytes, file_offset: int,
                            file_size: int, record_num: int):
        from cobrix_tpu.reader.header_parsers import RecordMetadata

        if len(header) < 5:
            return RecordMetadata(-1, False)
        length = header[3] | (header[4] << 8)
        return RecordMetadata(length, header[0] == 1)

    def on_receive_additional_info(self, additional_info: str) -> None:
        pass


def test_named_generator_ports_read_back_at_scale():
    """The four 1:1 named generator ports (BigEndian companies, 13a
    header+footer, 9 code pages, 8 non-printables) each produce files the
    reader consumes at multi-MB scale — no golden dependence."""
    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing import generators as g
    import tempfile, os

    cases = [
        (3000, g.generate_companies_big_endian(3000, seed=5),
         dict(copybook_contents=g.EXP2_COPYBOOK, is_record_sequence="true",
              is_rdw_big_endian="true", segment_field="SEGMENT-ID",
              redefine_segment_id_map="STATIC-DETAILS => C",
              **{"redefine_segment_id_map:1": "CONTACTS => P"})),
        (2000, g.generate_file_header_and_footer(2000, seed=5),
         dict(copybook_contents=g.TRANSDATA_COPYBOOK,
              file_start_offset="10", file_end_offset="12")),
        (2000, g.generate_code_pages(2000, seed=5),
         dict(copybook_contents=g.TRANSDATA_COPYBOOK,
              ebcdic_code_page="cp037")),
        (2000, g.generate_non_printable_names(2000, seed=5),
         dict(copybook_contents=g.TRANSDATA_COPYBOOK)),
    ]
    for expected, data, kw in cases:
        path = tempfile.mktemp(suffix=".dat")
        with open(path, "wb") as f:
            f.write(data)
        try:
            res = read_cobol(path, **kw)
            tbl = res.to_arrow()
            assert tbl.num_rows == expected  # every record decodes
            assert len(res.to_rows()) == expected
        finally:
            os.unlink(path)
