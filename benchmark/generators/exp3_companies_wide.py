"""Upstream exp3 (TestDataGen4CompaniesWide): RDW multisegment wide.

A 'C' record carries 2000 (COMP + COMP-3) strategy elements (a 16,064 B
payload behind its 4 B RDW header) and is followed by zero to four 'P'
contact records (60 B payloads). Frozen copy of cobrix_tpu.testing.generators.
generate_exp3 as of PR 21: the same draws in the same order, so the same
seed gives the same bytes; beside the bytes it returns what it drew, which
the post-window check holds the decoded table to without the program.
"""
import numpy as np

from .ebcdic import (ebcdic_encode, encode_comp3_unsigned, encode_comp_be,
                     sample_indices)

COPYBOOK = """
        01  COMPANY-DETAILS.
            05  SEGMENT-ID        PIC X(5).
            05  COMPANY-ID        PIC X(10).
            05  STATIC-DETAILS.
               10  COMPANY-NAME      PIC X(15).
               10  ADDRESS           PIC X(25).
               10  TAXPAYER.
                  15  TAXPAYER-TYPE  PIC X(1).
                  15  TAXPAYER-STR   PIC X(8).
                  15  TAXPAYER-NUM  REDEFINES TAXPAYER-STR
                                     PIC 9(8) COMP.
               10  STRATEGY.
                 15  STRATEGY-DETAIL OCCURS 2000.
                   25  NUM1 PIC 9(7) COMP.
                   25  NUM2 PIC 9(7) COMP-3.
            05  CONTACTS REDEFINES STATIC-DETAILS.
               10  PHONE-NUMBER      PIC X(17).
               10  CONTACT-PERSON    PIC X(28).
"""

DETAIL_COUNT = 2000
# one record in three is a 'C' (16,064 B + RDW), the others 'P' (64 B + RDW)
MEAN_RECORD_BYTES = 5400

_COMPANIES = ["ABCD Ltd.", "ECRONO GmbH", "ZjkLPj Ltd.", "Eqartion Inc.",
              "Test Bank", "Pear GMBH.", "Beiereqweq.", "Joan Q & Z",
              "Robotrd Inc.", "Xingzhoug", "MapMot Inc.", "Dobry Pivivar",
              "Xingzhoug", "Hadlway Hotels"]
_FIRST = ["Jene", "Maya", "Starr", "Lynell", "Eliana", "Tyesha", "Beatrice",
          "Otelia", "Timika", "Wilbert", "Mindy", "Sunday"]
_LAST = ["Corle", "Mackinnon", "Mork", "Shapiro", "Boettcher", "Flatt",
         "Acuna", "Thorpe", "Riojas", "Lepe", "Maccarthy", "Filipski"]


def _rdw(length: int) -> bytes:
    return bytes([0, 0, length & 0xFF, length >> 8])


def records_for(target_bytes: int) -> int:
    """Records that come to about `target_bytes` at the mean record size."""
    return int(target_bytes / MEAN_RECORD_BYTES) + 8


def generate(num_records: int, seed: int):
    """(file bytes, facts): `num_records` RDW records from `seed`."""
    rng = np.random.default_rng(seed)
    chunks = []
    facts = {"records": 0, "bytes": 0, "c_records": 0, "p_records": 0,
             "c_bytes": 0, "p_bytes": 0, "num_sum": 0}
    i = 0
    while i < num_records:
        company = _COMPANIES[rng.integers(0, len(_COMPANIES))]
        company_id = f"{rng.integers(10000, 99999)}{rng.integers(10000, 99999)}"
        payload = bytearray()
        payload += ebcdic_encode("C", 5)
        payload += ebcdic_encode(company_id, 10)
        payload += ebcdic_encode(company, 15)
        payload += ebcdic_encode(f"{rng.integers(1, 500)} Main Street", 25)
        taxpayer = int(rng.integers(10000000, 99999999))
        if rng.integers(0, 2) == 1:
            payload += ebcdic_encode("A", 1)
            payload += ebcdic_encode(str(taxpayer), 8)
        else:
            payload += ebcdic_encode("N", 1)
            payload += taxpayer.to_bytes(4, "big") + b"\x00\x00\x00\x00"
        nums = rng.integers(0, 9999999, size=DETAIL_COUNT)
        payload += np.concatenate(
            [encode_comp_be(nums, 4), encode_comp3_unsigned(nums, 7)],
            axis=1).tobytes()
        chunks.append(_rdw(len(payload)) + bytes(payload))
        facts["c_records"] += 1
        facts["c_bytes"] += len(payload)
        facts["num_sum"] += int(nums.sum())
        i += 1
        n_contacts = int(rng.integers(0, 5))
        for _ in range(n_contacts):
            if i >= num_records:
                break
            contact = bytearray()
            contact += ebcdic_encode("P", 5)
            contact += ebcdic_encode(company_id, 10)
            phone = (f"+({rng.integers(1, 921)}) {rng.integers(100, 999)} "
                     f"{rng.integers(10, 99)} {rng.integers(10, 99)}")
            contact += ebcdic_encode(phone, 17)
            person = (_FIRST[rng.integers(0, len(_FIRST))] + " "
                      + _LAST[rng.integers(0, len(_LAST))])
            contact += ebcdic_encode(person, 28)
            chunks.append(_rdw(len(contact)) + bytes(contact))
            facts["p_records"] += 1
            facts["p_bytes"] += len(contact)
            i += 1
    data = b"".join(chunks)
    facts["records"] = i
    facts["bytes"] = len(data)
    return data, facts


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order."""
    return {key: sum(p[key] for p in parts) for key in parts[0]}


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a seeded sample of whole RDW records (header included) into
    `out_path`; returns their record indices. Walks the headers itself."""
    raw = np.memmap(path, dtype=np.uint8, mode="r")
    offsets = []
    pos = 0
    while pos < len(raw):
        length = int(raw[pos + 2]) | (int(raw[pos + 3]) << 8)
        offsets.append((pos, 4 + length))
        pos += 4 + length
    idx = sample_indices(len(offsets), size, seed)
    with open(out_path, "wb") as f:
        for i in idx:
            start, length = offsets[i]
            f.write(raw[start:start + length].tobytes())
    return idx


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the program:
    rows per segment, NUM1 == NUM2 element for element (both encode the
    same draw) and their sum. Returns the list of what does not hold."""
    import pyarrow.compute as pc

    wrong = []
    root = table.column("COMPANY_DETAILS")
    if table.num_rows != facts["records"]:
        wrong.append(f"rows {table.num_rows} != {facts['records']} written")
    segments = pc.utf8_trim_whitespace(pc.struct_field(root, ["SEGMENT_ID"]))
    counts = {v["values"]: v["counts"]
              for v in pc.value_counts(segments).to_pylist()}
    want = {k: v for k, v in (("C", facts["c_records"]),
                              ("P", facts["p_records"])) if v}
    if counts != want:
        wrong.append(f"segment counts {counts} != {want}")
    detail = pc.list_flatten(pc.struct_field(
        root, ["STATIC_DETAILS", "STRATEGY", "STRATEGY_DETAIL"]))
    num1 = pc.struct_field(detail, "NUM1")
    num2 = pc.struct_field(detail, "NUM2")
    if len(num1) != facts["c_records"] * DETAIL_COUNT or num1.null_count:
        wrong.append(f"{len(num1)} NUM1 values ({num1.null_count} null) "
                     f"for {facts['c_records']} 'C' records")
    if not pc.all(pc.equal(num1, pc.cast(num2, num1.type))).as_py():
        wrong.append("NUM1 != NUM2 somewhere")
    got = pc.sum(pc.cast(num1, "int64")).as_py()
    if got != facts["num_sum"]:
        wrong.append(f"sum(NUM1) {got} != {facts['num_sum']} drawn")
    return wrong
