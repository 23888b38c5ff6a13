"""Upstream's hierarchical example (README "Reading hierarchical data
sets"; copybook `data/test17_hierarchical.cob`; generator
`TestDataGen17Hierarchical`; spec `Test17HierarchicalSpec`): seven
segment redefines of one 107 B area behind `SEGMENT-ID PIC 9(1)`, each
record written at its own segment's length behind a 4 B little-endian
RDW, in three levels:

    1 COMPANY  55 B   root
    2 DEPT     29 B   0-4 a company        5 CUSTOMER  61 B   0-4 a company
    3 EMPLOYEE 108 B  0-6 a department     6 CONTACT   50 B   0-2 a customer
    4 OFFICE   38 B   0-3 a department     7 CONTRACT  41 B   0-4 a customer

A company is followed by its departments (each by its employees, then its
offices), then by its customers (each by its contacts, then its
contracts): 20 records and 1,341 B at the mean, RDWs included. The layout,
the value domains and the uniform child counts are upstream's, as
`cobrix_tpu.testing.generators.generate_hierarchical` ports them; the
draws are NOT that port's. It emits record by record in a Python loop at
about 1.2 MB/s; this one draws the counts of a whole chunk level by level
with numpy and builds each segment's records as one byte matrix, so the
same seed gives other bytes than the port and always the same bytes here.

`generate(companies, seed)` writes whole companies: a chunk begins with a
COMPANY record and ends where a company ends, and `records` in its facts
is what the drivers hold a table's rows to: one row a ROOT record. The
facts are what was drawn: records per segment, the six child counts of
every parent in file order, every company's byte offset, the sums of
`TAXPAYER` and `AMOUNT`, and each chunk's seed, size and CRC.

`check_table` holds a decoded table to them without the program: rows,
the flattened length of each of the six lists and the length of every
parent's list in order, `Record_Id` of every row (upstream's
flush-trigger id: the index of the NEXT root record, for the last row the
file's record count), the sums, and `reference_rows` tree for tree on a
seeded sample of companies spread over the file plus the two companies
either side of every multiple of the 100 MiB index split. The reference
reads bytes, and `check_table` is handed a table and facts, no path: it
makes a chunk's bytes again from the chunk's seed and holds them to the
CRC of what was written.

`reference_rows` is the plain reference of the ASSEMBLY: `backend="numpy"`
and the device backends share `hierarchical_table`, so the whole-table
comparison ties the decode to the host kernels and says nothing of the
nesting. Nothing here imports the program.
"""
import decimal
import zlib

import numpy as np

from .ebcdic import ENCODE_LUT, encode_comp3_unsigned, encode_comp_be

COPYBOOK = """
     01  ENTITY.
         05  SEGMENT-ID           PIC 9(1).
         05  COMPANY.
            10  COMPANY-NAME      PIC X(20).
            10  ADDRESS           PIC X(30).
            10  TAXPAYER          PIC 9(9) BINARY.
         05  DEPT REDEFINES COMPANY.
            10  DEPT-NAME         PIC X(22).
            10  EXTENSION         PIC 9(6).
         05  EMPLOYEE REDEFINES COMPANY.
            10  FIRST-NAME        PIC X(16).
            10  LAST-NAME         PIC X(16).
            10  ROLE              PIC X(18).
            10  HOME-ADDRESS      PIC X(40).
            10  PHONE-NUM         PIC X(17).
         05  OFFICE REDEFINES COMPANY.
            10  ADDRESS           PIC X(30).
            10  FLOOR             PIC 9(3).
            10  ROOM-NUMBER       PIC 9(4).
         05  CUSTOMER REDEFINES COMPANY.
            10  CUSTOMER-NAME     PIC X(20).
            10  POSTAL-ADDRESS    PIC X(30).
            10  ZIP               PIC X(10).
         05  CONTACT REDEFINES COMPANY.
            10  FIRST-NAME        PIC X(16).
            10  LAST-NAME         PIC X(16).
            10  PHONE-NUM         PIC X(17).
         05  CONTRACT REDEFINES COMPANY.
            10  CONTRACT-NUMBER   PIC X(15).
            10  STATE             PIC X(8).
            10  DUE-DATE          PIC X(10).
            10  AMOUNT            PIC 9(10)V9(2) COMP-3.
"""

SEGMENTS = ("COMPANY", "DEPT", "EMPLOYEE", "OFFICE", "CUSTOMER", "CONTACT",
            "CONTRACT")                 # segment id 1..7, in this order
PARENT = {"DEPT": "COMPANY", "EMPLOYEE": "DEPT", "OFFICE": "DEPT",
          "CUSTOMER": "COMPANY", "CONTACT": "CUSTOMER",
          "CONTRACT": "CUSTOMER"}
# (name, offset in the payload, width, kind) of every segment's fields;
# the id byte is offset 0, so a redefine's first field is at 1
FIELDS = {
    "COMPANY": (("COMPANY_NAME", 1, 20, "text"), ("ADDRESS", 21, 30, "text"),
                ("TAXPAYER", 51, 4, "binary")),
    "DEPT": (("DEPT_NAME", 1, 22, "text"), ("EXTENSION", 23, 6, "display")),
    "EMPLOYEE": (("FIRST_NAME", 1, 16, "text"), ("LAST_NAME", 17, 16, "text"),
                 ("ROLE", 33, 18, "text"), ("HOME_ADDRESS", 51, 40, "text"),
                 ("PHONE_NUM", 91, 17, "text")),
    "OFFICE": (("ADDRESS", 1, 30, "text"), ("FLOOR", 31, 3, "display"),
               ("ROOM_NUMBER", 34, 4, "display")),
    "CUSTOMER": (("CUSTOMER_NAME", 1, 20, "text"),
                 ("POSTAL_ADDRESS", 21, 30, "text"), ("ZIP", 51, 10, "text")),
    "CONTACT": (("FIRST_NAME", 1, 16, "text"), ("LAST_NAME", 17, 16, "text"),
                ("PHONE_NUM", 33, 17, "text")),
    "CONTRACT": (("CONTRACT_NUMBER", 1, 15, "text"), ("STATE", 16, 8, "text"),
                 ("DUE_DATE", 24, 10, "text"), ("AMOUNT", 34, 7, "comp3")),
}
# bytes of each segment's record with its 4 B RDW, by segment id - 1
RECORD_BYTES = np.array([4 + max(off + width for _, off, width, _ in
                                 FIELDS[name]) for name in SEGMENTS])
# the most of each child a parent has (uniform on 0..max), as upstream's
MAX_CHILDREN = {"DEPT": 4, "EMPLOYEE": 6, "OFFICE": 3, "CUSTOMER": 4,
                "CONTACT": 2, "CONTRACT": 4}
MEAN_COMPANY_BYTES = 1341.0     # 59 + 2 x (33 + 3 x 112 + 1.5 x 42)
#                                    + 2 x (65 + 1 x 54 + 2 x 45)
INDEX_SPLIT_BYTES = 100 << 20   # where the reader's index cuts a file
REFERENCE_COMPANIES = 2000      # check_table's seeded sample, a file

_COMPANIES = ["ABCD Ltd.", "ECRONO GmbH", "ZjkLPj Ltd.", "Eqartion Inc.",
              "Test Bank", "Pear GMBH.", "Beiereqweq.", "Joan Q & Z",
              "Robotrd Inc.", "Xingzhoug", "MapMot Inc.", "Dobry Pivivar",
              "Xingzhoug", "Hadlway Hotels"]
_FIRST = ["Jene", "Maya", "Starr", "Lynell", "Eliana", "Tyesha", "Beatrice",
          "Otelia", "Timika", "Wilbert", "Mindy", "Sunday", "Tyson", "Cliff",
          "Mabelle", "Verdie", "Sulema", "Alona", "Suk", "Deandra",
          "Doretha", "Cassey", "Janiece", "Deshawn", "Willis", "Carrie",
          "Gabriele", "Inge", "Edyth", "Estelle"]
_LAST = ["Corle", "Mackinnon", "Mork", "Shapiro", "Boettcher", "Flatt",
         "Acuna", "Thorpe", "Riojas", "Lepe", "Maccarthy", "Filipski"]
_DEPARTMENTS = ["Executive", "Finance", "Operations", "Development",
                "Sales", "Marketing", "Research", "Risk Management",
                "Production", "Logistics", "Transportation", "Planning",
                "Engineering", "Accounting", "Legal", "Compliance",
                "Creative"]
_ROLES = ["CEO", "CFO", "CTO", "COO", "VP of Sales", "VP of Operations",
          "VP of Marketing", "VP of Development", "VP of Legal",
          "VP of Accounting", "director", "managing director",
          "software developer", "software engineer", "big data engineer",
          "devops", "support", "project manager", "scrum master", "sales",
          "copyrightor", "accountant", "analytic", "legal", "assistant",
          "researcher", "specialist"]
_STATES = ["Unsigned", "Signed", "Progress", "Rejected", "Done", "Archived"]

_EBCDIC_ZERO = 0xF0
_ROOT_ID = _EBCDIC_ZERO + 1


def _table(texts: list, width: int) -> np.ndarray:
    """[len(texts), width] EBCDIC, NUL-padded as upstream's generator
    pads: every value a field takes."""
    out = np.zeros((len(texts), width), dtype=np.uint8)
    for i, text in enumerate(texts):
        raw = np.frombuffer(text.encode("ascii"), dtype=np.uint8)
        out[i, :len(raw)] = ENCODE_LUT[raw]
    return out


_STREETS = [f"{n} Main Street" for n in range(1, 500)]


def _digits(values: np.ndarray, count: int) -> np.ndarray:
    """[N] ints -> [N, count] EBCDIC digits, most significant first."""
    out = np.empty((len(values), count), dtype=np.uint8)
    v = values.astype(np.int64)
    for pos in range(count - 1, -1, -1):
        out[:, pos] = _EBCDIC_ZERO + v % 10
        v = v // 10
    return out


def _number_text(values: np.ndarray, most: int, width: int) -> np.ndarray:
    """[N] ints of at most `most` digits -> [N, width] EBCDIC, as `str()`
    writes them: no leading zeros, NUL behind."""
    padded = _digits(values, most)
    count = np.ones(len(values), dtype=np.int64)
    for power in range(1, most):
        count += values >= 10 ** power
    at = np.arange(most)[None, :]
    shifted = np.take_along_axis(
        padded, np.minimum(most - count[:, None] + at, most - 1), axis=1)
    out = np.zeros((len(values), width), dtype=np.uint8)
    out[:, :most] = np.where(at < count[:, None], shifted, 0)
    return out


def _phones(rng, n: int) -> np.ndarray:
    """[n, 17] "+(a) ddd dd dd", a on 1..920."""
    area = rng.integers(1, 921, size=n)
    out = np.zeros((n, 17), dtype=np.uint8)
    out[:, 0:2] = ENCODE_LUT[np.frombuffer(b"+(", dtype=np.uint8)]
    out[:, 2:5] = _number_text(area, 3, 3)
    tail = np.full((n, 11), ENCODE_LUT[ord(" ")], dtype=np.uint8)
    tail[:, 0] = ENCODE_LUT[ord(")")]
    tail[:, 2:5] = _digits(rng.integers(100, 999, size=n), 3)
    tail[:, 6:8] = _digits(rng.integers(10, 99, size=n), 2)
    tail[:, 9:11] = _digits(rng.integers(10, 99, size=n), 2)
    tail_at = 3 + (area >= 10) + (area >= 100)
    out[np.arange(n)[:, None], tail_at[:, None] + np.arange(11)[None, :]] = \
        tail
    return out


def _picked(rng, table: np.ndarray, n: int) -> np.ndarray:
    return table[rng.integers(0, len(table), size=n)]


_TABLES = dict(
    company=_table(_COMPANIES, 20), street30=_table(_STREETS, 30),
    street40=_table(_STREETS, 40), dept=_table(_DEPARTMENTS, 22),
    first=_table(_FIRST, 16), last=_table(_LAST, 16),
    role=_table(_ROLES, 18), state=_table(_STATES, 8))


def records_for(target_bytes: int) -> int:
    """COMPANIES (root records: a table's rows) that come to about
    `target_bytes` at the mean of the child counts."""
    return max(1, int(target_bytes / MEAN_COMPANY_BYTES))


def _ranks(counts: np.ndarray):
    """(parent index, rank among its parent's children) of every child,
    children in parent order."""
    counts = counts.astype(np.int64)
    parent = np.repeat(np.arange(len(counts)), counts)
    first = np.cumsum(counts) - counts
    return parent, np.arange(int(counts.sum())) - first[parent]


def _per_parent(values: np.ndarray, parent: np.ndarray, n: int):
    """(sum of `values` over each of `n` parents' children, the running
    sum before each child within its parent)."""
    running = np.cumsum(values) - values
    total = np.bincount(parent, weights=values, minlength=n).astype(np.int64)
    first = np.cumsum(total) - total
    return total, running - first[parent]


def _layout(counts: dict, unit: np.ndarray):
    """Where every record of a chunk lies, in `unit` a record by segment
    (`RECORD_BYTES` for byte offsets, ones for record indices): a list of
    seven arrays, by segment. A company is followed by its departments
    (each by its employees, then its offices), then by its customers
    (each by its contacts, then its contracts)."""
    u = [int(x) for x in unit]
    counts = {child: drawn.astype(np.int64)     # uint8 as drawn
              for child, drawn in counts.items()}
    n = len(counts["DEPT"])
    dept_of, _ = _ranks(counts["DEPT"])
    cust_of, _ = _ranks(counts["CUSTOMER"])
    emp_of, emp_rank = _ranks(counts["EMPLOYEE"])
    off_of, off_rank = _ranks(counts["OFFICE"])
    con_of, con_rank = _ranks(counts["CONTACT"])
    ctr_of, ctr_rank = _ranks(counts["CONTRACT"])
    dept_size = u[1] + u[2] * counts["EMPLOYEE"] + u[3] * counts["OFFICE"]
    cust_size = u[4] + u[5] * counts["CONTACT"] + u[6] * counts["CONTRACT"]
    depts_size, dept_before = _per_parent(dept_size, dept_of, n)
    custs_size, cust_before = _per_parent(cust_size, cust_of, n)
    company_size = u[0] + depts_size + custs_size
    company = np.cumsum(company_size) - company_size
    dept = company[dept_of] + u[0] + dept_before
    customer = company[cust_of] + u[0] + depts_size[cust_of] + cust_before
    return [company, dept,
            dept[emp_of] + u[1] + u[2] * emp_rank,
            dept[off_of] + u[1] + u[2] * counts["EMPLOYEE"][off_of]
            + u[3] * off_rank,
            customer,
            customer[con_of] + u[4] + u[5] * con_rank,
            customer[ctr_of] + u[4] + u[5] * counts["CONTACT"][ctr_of]
            + u[6] * ctr_rank], int(company_size.sum())


def generate(companies: int, seed: int):
    """(file bytes, facts): `companies` whole companies from `seed`."""
    rng = np.random.default_rng(seed)
    t = _TABLES

    def drawn(child: str, parents: int) -> np.ndarray:
        return rng.integers(0, MAX_CHILDREN[child] + 1, size=parents,
                            dtype=np.uint8)

    counts = {"DEPT": drawn("DEPT", companies),
              "CUSTOMER": drawn("CUSTOMER", companies)}
    n_dept = int(counts["DEPT"].sum())
    n_cust = int(counts["CUSTOMER"].sum())
    counts.update(EMPLOYEE=drawn("EMPLOYEE", n_dept),
                  OFFICE=drawn("OFFICE", n_dept),
                  CONTACT=drawn("CONTACT", n_cust),
                  CONTRACT=drawn("CONTRACT", n_cust))
    n = [companies, n_dept, int(counts["EMPLOYEE"].sum()),
         int(counts["OFFICE"].sum()), n_cust, int(counts["CONTACT"].sum()),
         int(counts["CONTRACT"].sum())]

    def rows(segment: int) -> np.ndarray:
        """The segment's records, RDW and id byte written."""
        size = int(RECORD_BYTES[segment])
        out = np.zeros((n[segment], size), dtype=np.uint8)
        out[:, 2] = size - 4
        out[:, 4] = _ROOT_ID + segment
        return out

    body = 5    # a payload's first field: behind the RDW and the id byte
    taxpayer = rng.integers(100000000, 999999999, size=n[0])
    company = rows(0)
    company[:, body:body + 20] = _picked(rng, t["company"], n[0])
    company[:, body + 20:body + 50] = _picked(rng, t["street30"], n[0])
    company[:, body + 50:body + 54] = encode_comp_be(taxpayer, 4)
    dept = rows(1)
    dept[:, body:body + 22] = _picked(rng, t["dept"], n[1])
    dept[:, body + 22:body + 28] = _digits(
        rng.integers(100000, 999999, size=n[1]), 6)
    employee = rows(2)
    employee[:, body:body + 16] = _picked(rng, t["first"], n[2])
    employee[:, body + 16:body + 32] = _picked(rng, t["last"], n[2])
    employee[:, body + 32:body + 50] = _picked(rng, t["role"], n[2])
    employee[:, body + 50:body + 90] = _picked(rng, t["street40"], n[2])
    employee[:, body + 90:body + 107] = _phones(rng, n[2])
    office = rows(3)
    office[:, body:body + 30] = _picked(rng, t["street30"], n[3])
    office[:, body + 30:body + 33] = _digits(
        rng.integers(0, 120, size=n[3]), 3)
    office[:, body + 33:body + 37] = _digits(
        rng.integers(0, 3000, size=n[3]), 4)
    customer = rows(4)
    customer[:, body:body + 20] = _picked(rng, t["company"], n[4])
    customer[:, body + 20:body + 50] = _picked(rng, t["street30"], n[4])
    customer[:, body + 50:body + 60] = _number_text(
        rng.integers(100000000, 999999999, size=n[4]), 9, 10)
    contact = rows(5)
    contact[:, body:body + 16] = _picked(rng, t["first"], n[5])
    contact[:, body + 16:body + 32] = _picked(rng, t["last"], n[5])
    contact[:, body + 32:body + 49] = _phones(rng, n[5])
    contract = rows(6)
    contract[:, body:body + 15] = _number_text(
        rng.integers(0, 1000000, size=n[6]), 6, 15)
    contract[:, body + 15:body + 23] = _picked(rng, t["state"], n[6])
    dash = ENCODE_LUT[ord("-")]
    contract[:, body + 23:body + 27] = _digits(
        rng.integers(1990, 2020, size=n[6]), 4)
    contract[:, body + 27] = dash
    contract[:, body + 28:body + 30] = _digits(
        rng.integers(1, 13, size=n[6]), 2)
    contract[:, body + 30] = dash
    contract[:, body + 31:body + 33] = _digits(
        rng.integers(1, 29, size=n[6]), 2)
    # four kinds of amount, as upstream draws them, in cents
    kind = rng.integers(0, 4, size=n[6])
    wide = rng.integers(0, 89999999, size=n[6])
    amount = np.select(
        [kind == 0, kind == 1, kind == 2],
        [wide + 10000, rng.integers(0, 99, size=n[6]) * 100 + 10000,
         rng.integers(0, 89999, size=n[6]) + 100000], wide + 10000000)
    contract[:, body + 33:body + 40] = encode_comp3_unsigned(amount, 12)

    offsets, size = _layout(counts, RECORD_BYTES)
    data = np.empty(size, dtype=np.uint8)
    for at, matrix in zip(offsets, (company, dept, employee, office,
                                    customer, contact, contract)):
        data[at[:, None] + np.arange(matrix.shape[1])[None, :]] = matrix
    data = data.tobytes()
    facts = {
        "records": companies, "bytes": len(data),
        "segment_records": np.array(n, dtype=np.int64),
        # per company, per department and per customer, in file order
        **{f"{child.lower()}_counts": counts[child] for child in PARENT},
        "company_offset": offsets[0],
        "taxpayer_sum": int(taxpayer.sum()),
        "amount_sum": int(amount.sum()),
        "chunks": [{"seed": seed, "companies": companies,
                    "bytes": len(data), "crc32": zlib.crc32(data)}],
    }
    return data, facts


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order: every
    chunk begins with a COMPANY, so the companies simply follow one
    another, each chunk's offsets moved behind the chunks before it."""
    merged = {}
    for key, value in parts[0].items():
        values = [p[key] for p in parts]
        if key == "company_offset":
            starts = np.cumsum([0] + [p["bytes"] for p in parts[:-1]])
            merged[key] = np.concatenate(
                [v + start for v, start in zip(values, starts)])
        elif key == "segment_records":
            merged[key] = np.sum(values, axis=0)
        elif isinstance(value, np.ndarray):
            merged[key] = np.concatenate(values)
        else:
            merged[key] = sum(values, start=[] if key == "chunks" else 0)
    return merged


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a PREFIX of whole companies, between half of `size` and `size`
    records as the seed draws, into `out_path`; returns its root-row
    indices. A prefix, because a row's `Record_Id` is the file index of
    the record behind its company: companies picked out of the file into
    a file of their own get other ids, and the oracle's answer for them is
    another answer. Walks the headers itself."""
    rng = np.random.default_rng(seed)
    target = int(rng.integers(max(1, size // 2), size + 1))
    raw = np.memmap(path, dtype=np.uint8, mode="r")
    pos = records = companies = 0
    end = (0, 0)                # (bytes, companies) where a company ended
    while pos < len(raw) and records <= target:
        if raw[pos + 4] == _ROOT_ID:
            if records:
                end = (pos, companies)
            companies += 1
        pos += 4 + (int(raw[pos + 2]) | (int(raw[pos + 3]) << 8))
        records += 1
    if pos >= len(raw) and records <= target:
        end = (pos, companies)
    with open(out_path, "wb") as f:
        f.write(raw[:end[0]].tobytes())
    return np.arange(end[1])


# -- the plain reference ---------------------------------------------------

# EBCDIC -> text, the inverse of what the generators encode with; every
# other byte reads as a space, which the trim then takes
_DECODE = [" "] * 256
for _ascii in range(32, 127):
    _DECODE[ENCODE_LUT[_ascii]] = chr(_ascii)


def _field(payload: bytes, offset: int, width: int, kind: str):
    raw = payload[offset:offset + width]
    if kind == "text":
        return "".join(_DECODE[b] for b in raw).strip()
    if kind == "display":
        value = 0
        for b in raw:
            value = value * 10 + (b - _EBCDIC_ZERO)
        return value
    if kind == "binary":
        return int.from_bytes(raw, "big")
    cents = 0                               # COMP-3, sign in the last nibble
    for b in raw[:-1]:
        cents = cents * 100 + (b >> 4) * 10 + (b & 0x0F)
    cents = cents * 10 + (raw[-1] >> 4)
    return decimal.Decimal(cents).scaleb(-2)


def _struct(segment: str, payload: bytes) -> dict:
    row = {name: _field(payload, offset, width, kind)
           for name, offset, width, kind in FIELDS[segment]}
    row.update({child: [] for child, parent in PARENT.items()
                if parent == segment})
    return row


def reference_rows(source, roots, first_record: int = 0) -> dict:
    """{root-row index: the row as `table.slice(i, 1).to_pylist()[0]`
    gives it} for the root rows `roots` (indices among the source's
    COMPANY records) of an RDW file of this copybook. `source`: a path, or
    the bytes themselves; `first_record`: the file index of its first
    record, where the bytes are a later piece of a file.

    A straightforward walk in plain Python: read the RDW, the id byte,
    slice each field of the record's own segment at its copybook offset;
    buffer a root and its children; a child goes under the nearest
    preceding record of its parent's type. Departures from upstream's
    `VarLenHierarchicalIterator` / `extractHierarchicalRecord`: upstream
    buffers raw records and scans them once a child field, stopping where
    an ancestor's id comes again, this keeps the last struct met of each
    type since the root, which is the same tree in a hierarchy of three
    levels (a child met before any parent of its type is dropped by
    both);
    only this copybook's codecs, written out by hand (text through the
    inverse of the generators' table with any other byte a space, trimmed
    on both sides; unsigned DISPLAY, big-endian BINARY, unsigned COMP-3);
    `File_Id` 0 and `Record_Id` the index of the record that triggers the
    flush, as upstream stamps it."""
    if isinstance(source, str):
        source = np.memmap(source, dtype=np.uint8, mode="r")
    data = bytes(source)
    wanted = set(int(r) for r in roots)
    out = {}
    pos, record, root = 0, first_record, -1
    row = None          # the wanted row being buffered, and the last
    last = {}           # struct met of each segment type inside it
    size = len(data)
    while pos < size:
        length = data[pos + 2] | (data[pos + 3] << 8)
        segment = data[pos + 4] - _EBCDIC_ZERO
        if segment == 1:
            if row is not None:
                row["Record_Id"] = record
                row = None
            root += 1
            if root in wanted:
                payload = data[pos + 4:pos + 4 + length]
                company = _struct("COMPANY", payload)
                row = out[root] = {
                    "File_Id": 0, "Record_Id": None,
                    "ENTITY": {"SEGMENT_ID": _field(payload, 0, 1, "display"),
                               "COMPANY": company}}
                last = {"COMPANY": company}
        elif row is not None:
            name = SEGMENTS[segment - 1]
            parent = last.get(PARENT[name])
            if parent is not None:
                struct = _struct(name, data[pos + 4:pos + 4 + length])
                parent[name].append(struct)
                last[name] = struct
        pos += 4 + length
        record += 1
    if row is not None:
        row["Record_Id"] = record
    return out


# -- what the generator knows of a decoded table ---------------------------

def _boundary_companies(offsets: np.ndarray, size: int) -> np.ndarray:
    """The two companies either side of every multiple of the index split
    inside the file: where a cut that is not at a root, or a `Record_Id`
    off by a shard's start, shows."""
    cuts = np.arange(INDEX_SPLIT_BYTES, size, INDEX_SPLIT_BYTES)
    at = np.searchsorted(offsets, cuts, side="left")
    near = (at[:, None] + np.arange(-2, 2)[None, :]).ravel()
    return np.unique(near[(near >= 0) & (near < len(offsets))])


def _first_records(facts: dict):
    """(the file index of every company's record, the file's records)
    from the counts drawn."""
    counts = {child: facts[f"{child.lower()}_counts"] for child in PARENT}
    starts, total = _layout(counts, np.ones(len(SEGMENTS), dtype=np.int64))
    return starts[0], total


def _reference_failures(table, facts: dict) -> list:
    """`reference_rows` against the table's rows on the seeded sample and
    the boundary companies, chunk by chunk: a chunk's bytes made again
    from its seed and held to the CRC of what was written."""
    companies = facts["records"]
    rng = np.random.default_rng(facts["chunks"][0]["seed"])
    picked = rng.choice(companies, size=min(REFERENCE_COMPANIES, companies),
                        replace=False)
    wanted = np.union1d(picked, _boundary_companies(facts["company_offset"],
                                                    facts["bytes"]))
    first_record, _ = _first_records(facts)
    wrong = []
    start = 0
    for chunk in facts["chunks"]:
        stop = start + chunk["companies"]
        rows = wanted[(wanted >= start) & (wanted < stop)]
        if len(rows):
            data, _ = generate(chunk["companies"], chunk["seed"])
            if zlib.crc32(data) != chunk["crc32"]:
                return [f"the chunk of seed {chunk['seed']} does not "
                        f"generate again to the bytes that were written"]
            expected = reference_rows(data, rows - start,
                                      int(first_record[start]))
            for r in rows:
                got = table.slice(int(r), 1).to_pylist()[0]
                if got != expected[int(r) - start]:
                    wrong.append(int(r))
        start = stop
    if wrong:
        return [f"{len(wrong)} of {len(wanted)} companies differ from the "
                f"plain reference's tree, the first row {wrong[0]}"]
    return []


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the program
    (module docstring). Returns the list of what does not hold."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if table.num_rows != facts["records"]:
        return [f"rows {table.num_rows} != {facts['records']} companies "
                f"written"]
    wrong = []
    written = dict(zip(SEGMENTS, facts["segment_records"].tolist()))
    first_record, total = _first_records(facts)
    trigger = np.append(first_record[1:], total)
    if total != int(facts["segment_records"].sum()):
        wrong.append("the counts drawn do not add up to the records written")
    got = table.column("Record_Id").combine_chunks()
    if not got.equals(pa.array(trigger)):
        differ = np.flatnonzero(got.to_numpy() != trigger)
        wrong.append(f"Record_Id differs from the flush-trigger id on "
                     f"{len(differ)} rows, the first row {differ[0]}: "
                     f"{got[int(differ[0])].as_py()} != "
                     f"{trigger[differ[0]]}")
    if pc.any(pc.not_equal(table.column("File_Id"), 0)).as_py():
        wrong.append("File_Id is not 0 on every row")
    entity = table.column("ENTITY").combine_chunks()
    lists = {"COMPANY": pc.struct_field(entity, ["COMPANY"])}
    for child, parent in PARENT.items():
        array = pc.struct_field(lists[parent], [child])
        lengths = pc.list_value_length(array).to_numpy(zero_copy_only=False)
        drawn = facts[f"{child.lower()}_counts"]
        lists[child] = pc.list_flatten(array)
        if len(lists[child]) != written[child]:
            wrong.append(f"{len(lists[child])} {child} structs != "
                         f"{written[child]} records written")
        if len(lengths) != len(drawn) or (lengths != drawn).any():
            at = (np.flatnonzero(lengths != drawn)[:1]
                  if len(lengths) == len(drawn) else [])
            wrong.append(f"the {child} lists' lengths differ from the "
                         f"counts drawn" + (f", the first {parent} {at[0]}"
                                            if len(at) else ""))
    got = pc.sum(pc.cast(pc.struct_field(lists["COMPANY"], ["TAXPAYER"]),
                         pa.int64())).as_py()
    if got != facts["taxpayer_sum"]:
        wrong.append(f"sum(TAXPAYER) {got} != {facts['taxpayer_sum']} drawn")
    got = pc.sum(pc.struct_field(lists["CONTRACT"], ["AMOUNT"]),
                 min_count=0).as_py()
    expected = decimal.Decimal(facts["amount_sum"]).scaleb(-2)
    if (got or 0) != expected:
        wrong.append(f"sum(AMOUNT) {got} != {expected} drawn")
    if wrong:
        return wrong
    return _reference_failures(table, facts)
