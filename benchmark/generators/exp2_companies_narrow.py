"""Upstream exp2 (TestDataGen3Companies): RDW multisegment narrow.

A company record ('C': a 64 B payload of strings behind its 4 B
little-endian RDW header) is followed by zero to four contact records
('P': 60 B payloads), uniformly. The layout, the value domains and the
record mix are upstream's, as `cobrix_tpu.testing.generators.generate_exp2`
writes them; the draws are NOT that generator's. It draws record by
record in a Python loop, 42 us a record: the 8.2 million records of a
512 MiB scan would take six minutes. This one draws each field of a whole
chunk at once with numpy, so the same seed gives other bytes than
`generate_exp2` and always the same bytes here.

Beside the bytes it returns what it drew: the number of contacts of every
company in file order, which alone fixes every row's segment and both
generated Seg_Id columns; the name and street number of every company and
the area code, digits and person of every contact, in file order, which
fix all four string fields of every row; and the sums of the company ids
and of the taxpayer numbers written as COMP. `check_table` holds the
decoded table to them without the program, row by row in every shard and
block and not only where the oracle's sample falls.

`sample` writes a PREFIX of whole companies and not a scattered sample:
with Seg_Id generation on, a row's `Seg_Id0` is `<prefix>_<file>_<index of
its root record in the file>` and `Seg_Id1` counts the contacts since that
root, so records picked out of the file into a file of their own get other
indices and other roots and the oracle's answer for them is another answer.
Only a prefix that ends where a company ends keeps every index and every
root: it is the one sample whose Seg_Ids the scalar oracle reproduces.
"""
import numpy as np

from .ebcdic import ENCODE_LUT, ebcdic_encode

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
            05  CONTACTS REDEFINES STATIC-DETAILS.
               10  PHONE-NUMBER      PIC X(17).
               10  CONTACT-PERSON    PIC X(28).
"""

C_RECORD_BYTES = 68     # 4 B RDW + 64 B payload
P_RECORD_BYTES = 64     # 4 B RDW + 60 B payload
MAX_CONTACTS = 4
# one 'C' and on average two 'P' to a company
MEAN_RECORD_BYTES = (C_RECORD_BYTES + 2 * P_RECORD_BYTES) / 3.0
# the Seg_Id prefix the configuration fixes, and the only file of a read
SEG_ID_ROOT = "A_0_"

_COMPANIES = ["ABCD Ltd.", "ECRONO GmbH", "ZjkLPj Ltd.", "Eqartion Inc.",
              "Test Bank", "Pear GMBH.", "Beiereqweq.", "Joan Q & Z",
              "Robotrd Inc.", "Xingzhoug", "MapMot Inc.", "Dobry Pivivar",
              "Xingzhoug", "Hadlway Hotels"]
_FIRST = ["Jene", "Maya", "Starr", "Lynell", "Eliana", "Tyesha", "Beatrice",
          "Otelia", "Timika", "Wilbert", "Mindy", "Sunday"]
_LAST = ["Corle", "Mackinnon", "Mork", "Shapiro", "Boettcher", "Flatt",
         "Acuna", "Thorpe", "Riojas", "Lepe", "Maccarthy", "Filipski"]

_EBCDIC_ZERO = 0xF0
_SEGMENT_C = ENCODE_LUT[ord("C")]
_SEGMENT_P = ENCODE_LUT[ord("P")]


def _table(texts: list, width: int) -> np.ndarray:
    """[len(texts), width] EBCDIC, NUL-padded: every value a field takes."""
    return np.frombuffer(b"".join(ebcdic_encode(t, width) for t in texts),
                         dtype=np.uint8).reshape(len(texts), width)


_PERSONS = [f"{a} {b}" for a in _FIRST for b in _LAST]
_NAME_TABLE = _table(_COMPANIES, 15)
_ADDRESS_TABLE = _table([f"{n} Main Street" for n in range(1, 500)], 25)
_PERSON_TABLE = _table(_PERSONS, 28)
_PHONE_AREA_TABLE = _table([f"+({n}) " for n in range(1, 921)], 7)


def _digits(values: np.ndarray, count: int) -> np.ndarray:
    """[N] ints -> [N, count] EBCDIC digits, most significant first."""
    out = np.empty((len(values), count), dtype=np.uint8)
    v = values.astype(np.int64)
    for pos in range(count - 1, -1, -1):
        out[:, pos] = _EBCDIC_ZERO + v % 10
        v = v // 10
    return out


def records_for(target_bytes: int) -> int:
    """Records that come to about `target_bytes` at the mean record size."""
    return int(target_bytes / MEAN_RECORD_BYTES) + 8


def _contact_counts(rng, num_records: int) -> np.ndarray:
    """Contacts of each company, so that companies and contacts come to
    exactly `num_records` records; the last company is cut short."""
    drawn = rng.integers(0, MAX_CONTACTS + 1, size=num_records,
                         dtype=np.uint8)
    ends = np.cumsum(drawn.astype(np.int64) + 1)
    companies = int(np.searchsorted(ends, num_records, side="left")) + 1
    contacts = drawn[:companies].copy()
    contacts[-1] -= int(ends[companies - 1]) - num_records
    return contacts


def generate(num_records: int, seed: int):
    """(file bytes, facts): `num_records` RDW records from `seed`, the
    first a 'C'."""
    rng = np.random.default_rng(seed)
    contacts = _contact_counts(rng, num_records)
    n_c = len(contacts)
    per_company = contacts.astype(np.int64) + 1
    c_rows = np.cumsum(per_company) - per_company
    is_c = np.zeros(num_records, dtype=bool)
    is_c[c_rows] = True
    p_rows = np.flatnonzero(~is_c)
    n_p = len(p_rows)

    # every record as a 68 B row; a 'P' row's last 4 B are dropped below
    rows = np.zeros((num_records, C_RECORD_BYTES), dtype=np.uint8)
    rows[:, 2] = np.where(is_c, C_RECORD_BYTES - 4, P_RECORD_BYTES - 4)
    rows[:, 4] = np.where(is_c, _SEGMENT_C, _SEGMENT_P)
    id_high = rng.integers(10000, 99999, size=n_c)
    id_low = rng.integers(10000, 99999, size=n_c)
    company_ids = np.concatenate([_digits(id_high, 5), _digits(id_low, 5)],
                                 axis=1)
    rows[:, 9:19] = np.repeat(company_ids, per_company, axis=0)

    company = np.empty((n_c, 49), dtype=np.uint8)
    name = rng.integers(0, len(_COMPANIES), size=n_c)
    company[:, 0:15] = _NAME_TABLE[name]
    street_number = rng.integers(1, 500, size=n_c)
    company[:, 15:40] = _ADDRESS_TABLE[street_number - 1]
    taxpayer = rng.integers(10000000, 99999999, size=n_c)
    as_text = rng.integers(0, 2, size=n_c) == 1
    company[:, 40] = np.where(as_text, ENCODE_LUT[ord("A")],
                              ENCODE_LUT[ord("N")])
    as_comp = np.zeros((n_c, 8), dtype=np.uint8)
    as_comp[:, :4] = taxpayer.astype(">u4").view(np.uint8).reshape(n_c, 4)
    company[:, 41:49] = np.where(as_text[:, None], _digits(taxpayer, 8),
                                 as_comp)
    rows[c_rows, 19:68] = company

    contact = np.zeros((n_p, 45), dtype=np.uint8)
    area = rng.integers(1, 921, size=n_p)
    contact[:, 0:7] = _PHONE_AREA_TABLE[area - 1]
    # "+(n) " is 5 to 7 characters; "ddd dd dd" follows it
    tail = np.full((n_p, 9), ENCODE_LUT[ord(" ")], dtype=np.uint8)
    digits = [rng.integers(100, 999, size=n_p),
              rng.integers(10, 99, size=n_p), rng.integers(10, 99, size=n_p)]
    tail[:, 0:3] = _digits(digits[0], 3)
    tail[:, 4:6] = _digits(digits[1], 2)
    tail[:, 7:9] = _digits(digits[2], 2)
    tail_at = 5 + (area >= 10) + (area >= 100)
    contact[np.arange(n_p)[:, None],
            tail_at[:, None] + np.arange(9)[None, :]] = tail
    person = (rng.integers(0, len(_FIRST), size=n_p) * len(_LAST)
              + rng.integers(0, len(_LAST), size=n_p))
    contact[:, 17:45] = _PERSON_TABLE[person]
    rows[p_rows, 19:64] = contact

    keep = np.ones((num_records, C_RECORD_BYTES), dtype=bool)
    keep[p_rows, P_RECORD_BYTES:] = False
    data = rows[keep].tobytes()
    facts = {
        "records": num_records, "bytes": len(data),
        "c_records": n_c, "p_records": n_p, "contacts": contacts,
        # per company, then per contact, in file order
        "company_name": name.astype(np.uint8),
        "street_number": street_number.astype(np.uint16),
        "phone_area": area.astype(np.uint16),
        "phone_digits": (digits[0] * 10000 + digits[1] * 100
                         + digits[2]).astype(np.uint32),
        "contact_person": person.astype(np.uint8),
        "company_id_sum": int((id_high * 100000 + id_low).sum()),
        "taxpayer_num_sum": int(taxpayer[~as_text].sum()),
    }
    return data, facts


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order: every
    chunk begins with a 'C', so the companies simply follow one another."""
    return {key: (np.concatenate([p[key] for p in parts])
                  if isinstance(value, np.ndarray)
                  else sum(p[key] for p in parts))
            for key, value in parts[0].items()}


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a prefix of whole companies (module docstring), between half
    of `size` and `size` records as the seed draws, into `out_path`;
    returns its record indices. Walks the headers itself."""
    rng = np.random.default_rng(seed)
    target = int(rng.integers(max(1, size // 2), size + 1))
    raw = np.memmap(path, dtype=np.uint8, mode="r")
    pos = records = 0
    end = (0, 0)                # (bytes, records) where a company ended
    while pos < len(raw) and records <= target:
        if raw[pos + 4] == _SEGMENT_C and records:
            end = (pos, records)
        pos += 4 + (int(raw[pos + 2]) | (int(raw[pos + 3]) << 8))
        records += 1
    if pos >= len(raw) and records <= target:
        end = (pos, records)
    with open(out_path, "wb") as f:
        f.write(raw[:end[0]].tobytes())
    return np.arange(end[1])


def _expected_seg_ids(contacts: np.ndarray):
    """(Seg_Id0, Seg_Id1, is 'C' row) of every row, from the contact counts
    alone: upstream's SegmentIdAccumulator over the whole file in order."""
    import pyarrow as pa
    import pyarrow.compute as pc

    per_company = contacts.astype(np.int64) + 1
    c_rows = np.cumsum(per_company) - per_company
    root = np.repeat(c_rows, per_company)
    child = np.arange(len(root), dtype=np.int64) - root
    seg_id0 = pc.binary_join_element_wise(
        pa.scalar(SEG_ID_ROOT), pc.cast(pa.array(root), pa.string()), "")
    seg_id1 = pc.binary_join_element_wise(
        seg_id0, pc.cast(pa.array(child, mask=child == 0), pa.string()),
        "_L1_")
    return seg_id0, seg_id1, child == 0


def _expected_strings(facts: dict) -> list:
    """[(redefine, field, on 'C' rows?, the texts drawn)] of the four string
    fields, one text per company or per contact in file order."""
    import pyarrow as pa
    import pyarrow.compute as pc

    def text(values):
        return pc.cast(pa.array(values), pa.string())

    def picked(texts, indices):
        return pa.array(texts, pa.string()).take(pa.array(indices))

    digits = facts["phone_digits"]
    return [
        ("STATIC_DETAILS", "COMPANY_NAME", True,
         picked(_COMPANIES, facts["company_name"])),
        ("STATIC_DETAILS", "ADDRESS", True, pc.binary_join_element_wise(
            text(facts["street_number"]), pa.scalar("Main Street"), " ")),
        ("CONTACTS", "PHONE_NUMBER", False, pc.binary_join_element_wise(
            pa.scalar("+("), text(facts["phone_area"]), pa.scalar(") "),
            text(digits // 10000), pa.scalar(" "),
            text(digits // 100 % 100), pa.scalar(" "), text(digits % 100),
            "")),
        ("CONTACTS", "CONTACT_PERSON", False,
         picked(_PERSONS, facts["contact_person"])),
    ]


def _differs(name: str, got, expected) -> list:
    """[] where the two arrays are equal, nulls included, else one line
    with the number of rows that differ and the first of them."""
    import pyarrow.compute as pc

    if len(got) != len(expected):
        return [f"{name}: {len(got)} values != {len(expected)} written"]
    if got.equals(expected):
        return []
    differ = pc.or_(pc.fill_null(pc.not_equal(got, expected), False),
                    pc.xor(pc.is_null(got), pc.is_null(expected)))
    at = pc.index(differ, True).as_py()
    return [f"{name}: {pc.sum(differ).as_py()} rows differ, the first row "
            f"{at}: {got[at].as_py()!r} != {expected[at].as_py()!r}"]


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the program:
    rows per segment, both Seg_Id columns of every row, which redefine is
    null where, the four string fields of every row as drawn, the sums of
    the company ids and of the taxpayer numbers written as COMP, and where
    TAXPAYER_NUM is null. Returns the list of what does not hold."""
    import pyarrow as pa
    import pyarrow.compute as pc

    wrong = []
    if table.num_rows != facts["records"]:
        return [f"rows {table.num_rows} != {facts['records']} written"]
    root = table.column("COMPANY_DETAILS").combine_chunks()
    segments = pc.utf8_trim_whitespace(pc.struct_field(root, ["SEGMENT_ID"]))
    counts = {v["values"]: v["counts"]
              for v in pc.value_counts(segments).to_pylist()}
    want = {k: v for k, v in (("C", facts["c_records"]),
                              ("P", facts["p_records"])) if v}
    if counts != want:
        wrong.append(f"segment counts {counts} != {want}")
    seg_id0, seg_id1, is_c = _expected_seg_ids(facts["contacts"])
    is_c = pa.array(is_c)
    for name, expected in (("Seg_Id0", seg_id0), ("Seg_Id1", seg_id1)):
        wrong += _differs(name, table.column(name).combine_chunks(),
                          expected)
    if not pc.all(pc.equal(pc.fill_null(pc.equal(segments, "C"), False),
                           is_c)).as_py():
        wrong.append("a 'C' row where the contact counts put a 'P', or "
                     "the other way round")
    for group, hidden_on_c in (("STATIC_DETAILS", False), ("CONTACTS", True)):
        null = pc.is_null(pc.struct_field(root, [group]))
        if not pc.all(pc.equal(null, is_c if hidden_on_c
                               else pc.invert(is_c))).as_py():
            wrong.append(f"{group} is not null exactly on the "
                         f"{'C' if hidden_on_c else 'P'} rows")
    for group, field, on_c, expected in _expected_strings(facts):
        got = pc.filter(pc.struct_field(root, [group, field]),
                        is_c if on_c else pc.invert(is_c))
        wrong += _differs(f"{field} of the {'C' if on_c else 'P'} rows",
                          got, expected)
    ids = pc.cast(pc.utf8_trim_whitespace(
        pc.struct_field(root, ["COMPANY_ID"])), pa.int64())
    got = pc.sum(pc.filter(ids, is_c)).as_py()
    if got != facts["company_id_sum"]:
        wrong.append(f"sum(COMPANY_ID of 'C' rows) {got} != "
                     f"{facts['company_id_sum']} drawn")
    taxpayer = ["STATIC_DETAILS", "TAXPAYER"]
    num = pc.struct_field(root, taxpayer + ["TAXPAYER_NUM"])
    got = pc.sum(pc.cast(num, pa.int64()), min_count=0).as_py()
    if got != facts["taxpayer_num_sum"]:
        wrong.append(f"sum(TAXPAYER_NUM) {got} != "
                     f"{facts['taxpayer_num_sum']} written as COMP")
    as_text = pc.fill_null(pc.equal(pc.utf8_trim_whitespace(
        pc.struct_field(root, taxpayer + ["TAXPAYER_TYPE"])), "A"), False)
    if not pc.all(pc.equal(pc.is_null(num),
                           pc.or_(pc.invert(is_c), as_text))).as_py():
        wrong.append("TAXPAYER_NUM is not null exactly where the type is "
                     "'A' or the row is 'P'")
    return wrong
