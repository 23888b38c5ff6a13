"""TPC-H CUSTOMER with each customer's orders, each order with its 1 to 7
LINEITEM rows (Standard Specification rev 3, clauses 1.4.1 and 4.2.3), as
ONE variable-length mainframe record: the customer's columns, a count,
the orders as `OCCURS 0 TO 40 TIMES DEPENDING ON` the count, each order
`tpch_orders_nested`'s record less `O-CUSTKEY` (its lines an
`OCCURS 1 TO 7 TIMES DEPENDING ON` of their own, its comment behind
them), and the customer's comment BEHIND the orders, so that where it
lies depends on every count of the record. RDW-framed (little-endian, as
upstream's generators write), EBCDIC cp037.

Frozen with the benchmark: nothing here imports the program. The orders
and their lines are `tpch_orders_nested`'s draws (its `draw`, and through
it `tpch_lineitem`'s); the customers' own columns and their order counts
are drawn here: no order for a customer whose key is a multiple of three
(clause 4.2.3), a Poisson count of mean 15 for every other, as SF 1's
1,500,000 orders spread over its 100,000 such customers, at most 40.

`generate(customers, seed)` returns the bytes and what was drawn: each
customer's key and order count, each order's line count, integer sums of
the customers' and the orders' numeric columns and of the lines', the
digest of every `C-COMMENT`, each customer's byte offset, and the chunk's
seed, size and CRC. `check_table` holds a decoded table to them, then
`reference_rows` tree for tree on a seeded sample of customers plus the
two either side of every multiple of the 100 MiB index split: the numpy
and the device backends share the element framing, so the whole-table
comparison says nothing of the nesting; `reference_rows` is a plain
walk of the bytes.

The record, 224 B (no order) to 46,184 B (40 orders of seven lines),
plus its 4 B RDW:

    C-CUSTKEY                       PIC S9(9) COMP             4
    C-NAME                          PIC X(25)                 25
    C-ADDRESS                       PIC X(40)                 40
    C-NATIONKEY                     PIC S9(9) COMP             4
    C-PHONE                         PIC X(15)                 15
    C-ACCTBAL                       PIC S9(10)V99 COMP-3       7
    C-MKTSEGMENT                    PIC X(10)                 10
    C-ORDER-COUNT                   PIC 9(2)                   2
    C-ORDERS OCCURS 0 TO 40 DEPENDING ON C-ORDER-COUNT   279 to 1,149 an order
    C-COMMENT                       PIC X(117), space padded 117
"""
import decimal
import zlib

import numpy as np

from . import tpch_lineitem as lineitem
from . import tpch_orders_nested as orders_nested
from .ebcdic import ENCODE_LUT, encode_comp_be, sample_indices

COPYBOOK = """
       01  CUSTOMER.
           05  C-CUSTKEY        PIC S9(9) COMP.
           05  C-NAME           PIC X(25).
           05  C-ADDRESS        PIC X(40).
           05  C-NATIONKEY      PIC S9(9) COMP.
           05  C-PHONE          PIC X(15).
           05  C-ACCTBAL        PIC S9(10)V99 COMP-3.
           05  C-MKTSEGMENT     PIC X(10).
           05  C-ORDER-COUNT    PIC 9(2).
           05  C-ORDERS OCCURS 0 TO 40 TIMES
                        DEPENDING ON C-ORDER-COUNT.
               10  O-ORDERKEY       PIC S9(9) COMP.
               10  O-ORDERSTATUS    PIC X.
               10  O-TOTALPRICE     PIC S9(10)V99 COMP-3.
               10  O-ORDERDATE      PIC 9(8).
               10  O-ORDERPRIORITY  PIC X(15).
               10  O-CLERK          PIC X(15).
               10  O-SHIPPRIORITY   PIC S9(9) COMP.
               10  O-LINE-COUNT     PIC 9(1).
               10  O-LINES OCCURS 1 TO 7 TIMES
                           DEPENDING ON O-LINE-COUNT.
                   15  L-PARTKEY        PIC S9(9) COMP.
                   15  L-SUPPKEY        PIC S9(9) COMP.
                   15  L-LINENUMBER     PIC S9(9) COMP.
                   15  L-QUANTITY       PIC S9(10)V99 COMP-3.
                   15  L-EXTENDEDPRICE  PIC S9(10)V99 COMP-3.
                   15  L-DISCOUNT       PIC S9(10)V99 COMP-3.
                   15  L-TAX            PIC S9(10)V99 COMP-3.
                   15  L-RETURNFLAG     PIC X.
                   15  L-LINESTATUS     PIC X.
                   15  L-SHIPDATE       PIC 9(8).
                   15  L-COMMITDATE     PIC 9(8).
                   15  L-RECEIPTDATE    PIC 9(8).
                   15  L-SHIPINSTRUCT   PIC X(25).
                   15  L-SHIPMODE       PIC X(10).
                   15  L-COMMENT        PIC X(44).
               10  O-COMMENT        PIC X(79).
           05  C-COMMENT        PIC X(117).
"""
HEADER_BYTES = 107                      # the customer's columns and count
ORDER_HEADER_BYTES = orders_nested.HEADER_BYTES - 4   # less O-CUSTKEY
LINE_BYTES = orders_nested.LINE_BYTES
ORDER_COMMENT_BYTES = orders_nested.COMMENT_BYTES
COMMENT_BYTES = 117
MAX_ORDERS = 40
MEAN_ORDERS = 15                        # a customer that has orders
MIN_RECORD = HEADER_BYTES + COMMENT_BYTES
# the RDW and a record at the mean: two customers in three with 15
# orders of four lines
MEAN_RECORD_BYTES = 4 + MIN_RECORD + 10 * (
    ORDER_HEADER_BYTES + 4 * LINE_BYTES + ORDER_COMMENT_BYTES)
NATIONS = 25
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
COMMENT_SLOTS, SLOT_BYTES = 10, 11      # up to ten seeded words
ADDRESS_LETTERS = np.frombuffer(
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789,",
    dtype=np.uint8)
# the index split of a file of records this wide (100 MiB): the customers
# either side of each multiple of it are compared with `reference_rows`
INDEX_SPLIT_BYTES = 100 * 1024 * 1024
REFERENCE_CUSTOMERS = 2000
# the sums `check_table` holds a table to: (fact, column, scale or None)
CUSTOMER_SUMS = (("custkey", "C_CUSTKEY", None),
                 ("nationkey", "C_NATIONKEY", None),
                 ("acctbal", "C_ACCTBAL", 2),
                 ("orders", "C_ORDER_COUNT", None))
ORDER_SUMS = (("orderkey", "O_ORDERKEY", None),
              ("totalprice", "O_TOTALPRICE", 2),
              ("orderdate", "O_ORDERDATE", None),
              ("shippriority", "O_SHIPPRIORITY", None),
              ("lines", "O_LINE_COUNT", None))
LINE_SUMS = orders_nested.LINE_SUMS
PER_CUSTOMER = ("keys", "order_counts", "comment_digests", "offsets")


def digest(text: np.ndarray) -> np.ndarray:
    """[n] uint64 digests of [n, width] ASCII text, space padded."""
    powers = orders_nested.DIGEST_BASE ** np.arange(text.shape[1],
                                                    dtype=np.uint64)
    return (text.astype(np.uint64) * powers).sum(axis=1, dtype=np.uint64)


def _comp3_signed(cents: np.ndarray, width: int = 7) -> np.ndarray:
    """[n] ints to [n, width] packed decimal, sign nibble C or D."""
    out = lineitem._comp3(np.abs(cents), width)
    out[:, -1] = (out[:, -1] & 0xF0) | np.where(cents < 0, 0x0D, 0x0C)
    return out


def draw(customers: int, seed: int) -> dict:
    """The customers' own columns and order counts, and their orders'
    (tpch_orders_nested's draw over all of them, in customer order)."""
    rng = np.random.default_rng([seed, 0xC057])
    keys = np.arange(1, customers + 1)
    counts = np.where(keys % 3 == 0, 0,
                      np.minimum(rng.poisson(MEAN_ORDERS, size=customers),
                                 MAX_ORDERS))
    d = orders_nested.draw(max(int(counts.sum()), 1), seed)
    address_len = rng.integers(10, 41, size=customers)
    address = ADDRESS_LETTERS[rng.integers(0, len(ADDRESS_LETTERS),
                                           size=(customers, 40))]
    address[np.arange(40)[None, :] >= address_len[:, None]] = 0x20
    return {"orders": d, "keys": keys, "counts": counts,
            "nationkey": rng.integers(0, NATIONS, size=customers),
            "acctbal": rng.integers(-99999, 1000000, size=customers),
            "segment": rng.integers(0, len(SEGMENTS), size=customers),
            "address": address,
            "phone": rng.integers(0, 10 ** 10, size=customers),
            "words": rng.integers(0, len(lineitem.WORDS),
                                  size=(customers, COMMENT_SLOTS)),
            "word_count": rng.integers(1, COMMENT_SLOTS + 1,
                                       size=customers)}


def comments(d: dict) -> np.ndarray:
    """[n, 117] ASCII comments: the first `word_count` seeded words in
    11 B slots, space padded."""
    words = np.full((len(lineitem.WORDS), SLOT_BYTES), 0x20, dtype=np.uint8)
    for i, word in enumerate(lineitem.WORDS):
        words[i, :len(word)] = np.frombuffer(word.encode("ascii"), np.uint8)
    text = np.full((len(d["words"]), COMMENT_BYTES), 0x20, dtype=np.uint8)
    slots = words[d["words"]]
    slots[np.arange(COMMENT_SLOTS)[None, :] >= d["word_count"][:, None]] = \
        0x20
    text[:, :COMMENT_SLOTS * SLOT_BYTES] = slots.reshape(len(slots), -1)
    return text


def _text(values, width: int) -> np.ndarray:
    """[n] str (ASCII) to [n, width] EBCDIC, space padded."""
    raw = np.asarray(values, dtype=f"S{width}")
    codes = np.frombuffer(raw.tobytes(), dtype=np.uint8).reshape(-1, width)
    return ENCODE_LUT[np.where(codes == 0, 0x20, codes)]


def _orders(d: dict) -> list:
    """Each drawn order as its element's bytes, in order."""
    o = d["orders"]
    n = len(o["counts"])
    clerks = np.char.add("Clerk#", np.char.zfill(o["clerk"].astype(str), 9))
    header = np.concatenate([
        encode_comp_be(o["orderkey"], 4),
        ENCODE_LUT[o["status"].astype("S1").view(np.uint8)][:, None],
        lineitem._comp3(o["totalprice"]), lineitem._display(o["orderdate"]),
        lineitem._ebcdic(orders_nested.PRIORITIES, 15)[o["priority"]],
        _text(clerks, 15), encode_comp_be(o["shippriority"], 4),
        lineitem._display(o["counts"], 1)], axis=1)
    lines = lineitem.encode(o["lines"])[:, 4:]
    comment = ENCODE_LUT[orders_nested.comments(o)]
    elements = [None] * n
    for count in range(1, orders_nested.MAX_LINES + 1):
        idx = np.flatnonzero(o["counts"] == count)
        if not len(idx):
            continue
        of_lines = (o["starts"][idx][:, None]
                    + np.arange(count)[None, :]).reshape(-1)
        rows = np.concatenate(
            [header[idx], lines[of_lines].reshape(len(idx), -1),
             comment[idx]], axis=1)
        for k, i in enumerate(idx.tolist()):
            elements[i] = rows[k].tobytes()
    return elements


def encode(d: dict) -> bytes:
    """The drawn customers as RDW-framed record bytes, in order."""
    n = len(d["keys"])
    names = np.char.add("Customer#", np.char.zfill(d["keys"].astype(str), 9))
    phone = d["phone"]
    phones = [f"{10 + c:02d}-{p // 10 ** 7:03d}-{p // 10 ** 4 % 1000:03d}-"
              f"{p % 10 ** 4:04d}"
              for c, p in zip(d["nationkey"].tolist(), phone.tolist())]
    header = np.concatenate([
        encode_comp_be(d["keys"], 4), _text(names, 25),
        ENCODE_LUT[d["address"]], encode_comp_be(d["nationkey"], 4),
        _text(phones, 15), _comp3_signed(d["acctbal"]),
        lineitem._ebcdic(SEGMENTS, 10)[d["segment"]],
        lineitem._display(d["counts"], 2)], axis=1)
    if header.shape[1] != HEADER_BYTES:
        raise RuntimeError(f"a customer's header is {header.shape[1]} B")
    comment = ENCODE_LUT[comments(d)]
    elements = _orders(d)
    first = np.cumsum(d["counts"]) - d["counts"]
    records = []
    for i in range(n):
        body = b"".join([header[i].tobytes(),
                         *elements[first[i]:first[i] + d["counts"][i]],
                         comment[i].tobytes()])
        records += [bytes((0, 0, len(body) & 0xFF, len(body) >> 8)), body]
    return b"".join(records)


def generate(records: int, seed: int):
    """(the bytes of `records` customers drawn from `seed`, what is known
    of them without the program)."""
    d = draw(records, seed)
    data = encode(d)
    o = d["orders"]
    shown = int(d["counts"].sum())
    lines = {key: value[:int(o["counts"][:shown].sum())]
             for key, value in o["lines"].items()}
    order_facts = {key: o[key if key != "lines" else "counts"][:shown]
                   for key, _, _ in ORDER_SUMS}
    raw = np.frombuffer(data, dtype=np.uint8)
    facts = {"records": records, "bytes": len(data), "orders": shown,
             "line_rows": int(o["counts"][:shown].sum()),
             "keys": d["keys"].astype(np.int32),
             "order_counts": d["counts"].astype(np.uint8),
             "line_counts": o["counts"][:shown].astype(np.uint8),
             "comment_digests": digest(comments(d)),
             "offsets": orders_nested.record_offsets(raw),
             "chunks": [{"seed": seed, "customers": records,
                         "bytes": len(data), "crc32": zlib.crc32(data)}],
             "sums": {"custkey": int(d["keys"].sum()),
                      "nationkey": int(d["nationkey"].sum()),
                      "acctbal": int(d["acctbal"].sum()),
                      "orders": shown,
                      **{key: int(order_facts[key].sum())
                         for key, _, _ in ORDER_SUMS},
                      **{key: int(lines[key].sum())
                         for key, _, _ in LINE_SUMS}}}
    return data, facts


def records_for(target_bytes: int) -> int:
    """Customers that come to about `target_bytes` at the mean record."""
    return max(1, int(target_bytes / MEAN_RECORD_BYTES))


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order."""
    merged = {key: sum(p[key] for p in parts)
              for key in ("records", "bytes", "orders", "line_rows")}
    starts = np.cumsum([0] + [p["bytes"] for p in parts[:-1]])
    for key in PER_CUSTOMER + ("line_counts",):
        values = [p[key] for p in parts]
        if key == "offsets":
            values = [v + start for v, start in zip(values, starts)]
        merged[key] = np.concatenate(values)
    merged["chunks"] = sum((p["chunks"] for p in parts), [])
    merged["sums"] = {key: sum(p["sums"][key] for p in parts)
                      for key in parts[0]["sums"]}
    return merged


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a seeded sample of whole records, RDW and all, into
    `out_path`; returns their record indices."""
    raw = np.memmap(path, dtype=np.uint8, mode="r")
    offsets = orders_nested.record_offsets(raw)
    ends = np.append(offsets[1:], len(raw))
    idx = sample_indices(len(offsets), size, seed)
    with open(out_path, "wb") as f:
        for i in idx.tolist():
            f.write(raw[offsets[i]:ends[i]].tobytes())
    return idx


# -- the plain reference ---------------------------------------------------

# EBCDIC -> text, the inverse of what the generators encode with; every
# other byte reads as a space, which the trim then takes
_DECODE = [" "] * 256
for _ascii in range(32, 127):
    _DECODE[ENCODE_LUT[_ascii]] = chr(_ascii)

# (name, width, kind) in record order; kind "text", "binary", "display"
# or "packed" (S9(10)V99 COMP-3); "count" a DISPLAY count
CUSTOMER_FIELDS = (("C_CUSTKEY", 4, "binary"), ("C_NAME", 25, "text"),
                   ("C_ADDRESS", 40, "text"), ("C_NATIONKEY", 4, "binary"),
                   ("C_PHONE", 15, "text"), ("C_ACCTBAL", 7, "packed"),
                   ("C_MKTSEGMENT", 10, "text"), ("C_ORDER_COUNT", 2, "count"))
ORDER_FIELDS = (("O_ORDERKEY", 4, "binary"), ("O_ORDERSTATUS", 1, "text"),
                ("O_TOTALPRICE", 7, "packed"), ("O_ORDERDATE", 8, "display"),
                ("O_ORDERPRIORITY", 15, "text"), ("O_CLERK", 15, "text"),
                ("O_SHIPPRIORITY", 4, "binary"),
                ("O_LINE_COUNT", 1, "count"))
LINE_FIELDS = (("L_PARTKEY", 4, "binary"), ("L_SUPPKEY", 4, "binary"),
               ("L_LINENUMBER", 4, "binary"), ("L_QUANTITY", 7, "packed"),
               ("L_EXTENDEDPRICE", 7, "packed"), ("L_DISCOUNT", 7, "packed"),
               ("L_TAX", 7, "packed"), ("L_RETURNFLAG", 1, "text"),
               ("L_LINESTATUS", 1, "text"), ("L_SHIPDATE", 8, "display"),
               ("L_COMMITDATE", 8, "display"),
               ("L_RECEIPTDATE", 8, "display"),
               ("L_SHIPINSTRUCT", 25, "text"), ("L_SHIPMODE", 10, "text"),
               ("L_COMMENT", 44, "text"))


def _value(raw: bytes, kind: str):
    if kind == "text":
        return "".join(_DECODE[b] for b in raw).strip()
    if kind == "binary":
        return int.from_bytes(raw, "big", signed=True)
    if kind in ("display", "count"):
        value = 0
        for b in raw:
            value = value * 10 + (b - 0xF0)
        return value
    cents = 0                           # COMP-3, sign in the last nibble
    for b in raw[:-1]:
        cents = cents * 100 + (b >> 4) * 10 + (b & 0x0F)
    cents = cents * 10 + (raw[-1] >> 4)
    return decimal.Decimal(-cents if raw[-1] & 0x0F == 0x0D
                           else cents).scaleb(-2)


def _fields(data: bytes, pos: int, fields) -> tuple:
    """({name: value}, the position behind them) of `fields` from `pos`."""
    row = {}
    for name, width, kind in fields:
        row[name] = _value(data[pos:pos + width], kind)
        pos += width
    return row, pos


def reference_rows(source, rows) -> dict:
    """{row index: the row as `table.take([i]).to_pylist()[0]` gives it}
    for the customers `rows` (indices among the source's records) of an
    RDW file of this copybook, read with `collapse_root`. `source`: a
    path, or the bytes themselves.

    A straightforward walk in plain Python: read the RDW, then the
    customer's columns, its count, each order's columns, its count, its
    lines and its comment, then the customer's comment, each field sliced
    where the walk has got to. Only what the generator writes, written
    out by hand: counts within their bounds, every record whole; text
    through the inverse of the generators' table, any other byte a space,
    trimmed on both sides; big-endian signed BINARY, unsigned DISPLAY,
    COMP-3 with sign nibble C or D."""
    if isinstance(source, str):
        source = np.memmap(source, dtype=np.uint8, mode="r")
    data = bytes(source)
    wanted = sorted(set(int(r) for r in rows))
    out = {}
    pos, record, k = 0, 0, 0
    while pos < len(data) and k < len(wanted):
        length = data[pos + 2] | (data[pos + 3] << 8)
        if record == wanted[k]:
            at = pos + 4
            row, at = _fields(data, at, CUSTOMER_FIELDS)
            orders = []
            for _ in range(row["C_ORDER_COUNT"]):
                order, at = _fields(data, at, ORDER_FIELDS)
                lines = []
                for _ in range(order["O_LINE_COUNT"]):
                    line, at = _fields(data, at, LINE_FIELDS)
                    lines.append(line)
                order["O_LINES"] = lines
                order["O_COMMENT"] = _value(
                    data[at:at + ORDER_COMMENT_BYTES], "text")
                at += ORDER_COMMENT_BYTES
                orders.append(order)
            row["C_ORDERS"] = orders
            row["C_COMMENT"] = _value(data[at:at + COMMENT_BYTES], "text")
            out[record] = row
            k += 1
        pos += 4 + length
        record += 1
    return out


# -- what the generator knows of a decoded table ---------------------------

def _decimal(value: int, scale: int) -> decimal.Decimal:
    return decimal.Decimal(value).scaleb(-scale)


def _sum_failures(table, sums: dict, which, what: str) -> list:
    import pyarrow.compute as pc

    wrong = []
    for key, column, scale in which:
        values = table.column(column)
        if scale is None:
            values = pc.cast(values, "int64")
        total = pc.sum(values, min_count=0).as_py() or 0
        drawn = sums[key] if scale is None else _decimal(sums[key], scale)
        if total != drawn:
            wrong.append(f"sum({what}{column}) {total} != {drawn} drawn")
    return wrong


def _boundary_customers(offsets: np.ndarray, size: int) -> np.ndarray:
    """The two customers either side of every multiple of the index split
    inside the file: where a cut or a shard's first record shows."""
    cuts = np.arange(INDEX_SPLIT_BYTES, size, INDEX_SPLIT_BYTES)
    at = np.searchsorted(offsets, cuts, side="left")
    near = (at[:, None] + np.arange(-2, 2)[None, :]).ravel()
    return np.unique(near[(near >= 0) & (near < len(offsets))])


def _reference_failures(table, facts: dict) -> list:
    """`reference_rows` against the table's rows on a seeded sample and
    the boundary customers, chunk by chunk: a chunk's bytes made again
    from its seed and held to the CRC of what was written."""
    customers = facts["records"]
    rng = np.random.default_rng(facts["chunks"][0]["seed"])
    picked = rng.choice(customers, size=min(REFERENCE_CUSTOMERS, customers),
                        replace=False)
    wanted = np.union1d(picked, _boundary_customers(facts["offsets"],
                                                    facts["bytes"]))
    wrong = []
    start = 0
    for chunk in facts["chunks"]:
        stop = start + chunk["customers"]
        rows = wanted[(wanted >= start) & (wanted < stop)]
        if len(rows):
            data, _ = generate(chunk["customers"], chunk["seed"])
            if zlib.crc32(data) != chunk["crc32"]:
                return [f"the chunk of seed {chunk['seed']} does not "
                        f"generate again to the bytes that were written"]
            expected = reference_rows(data, rows - start)
            got = table.take(rows).to_pylist()
            wrong += [int(r) for r, row in zip(rows, got)
                      if row != expected[int(r) - start]]
        start = stop
    if wrong:
        return [f"{len(wrong)} of {len(wanted)} customers differ from the "
                f"plain reference's tree, the first row {wrong[0]}"]
    return []


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the
    program (module docstring). Returns the list of what does not hold."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if table.num_rows != facts["records"]:
        return [f"rows {table.num_rows} != {facts['records']} written"]
    wrong = []
    keys = table.column("C_CUSTKEY").to_numpy()
    if not np.array_equal(keys, facts["keys"]):
        wrong.append(f"{int((keys != facts['keys']).sum())} customers with "
                     "another C_CUSTKEY than drawn")
    lists = table.column("C_ORDERS").combine_chunks()
    lengths = pc.list_value_length(lists).to_numpy(zero_copy_only=False)
    if not np.array_equal(lengths, facts["order_counts"]):
        wrong.append(f"{int((lengths != facts['order_counts']).sum())} "
                     "customers whose list is not as long as the count drawn")
    orders = pa.Table.from_struct_array(pc.list_flatten(lists))
    if orders.num_rows != facts["orders"]:
        return wrong + [f"{orders.num_rows} orders != {facts['orders']} "
                        "drawn"]
    lines_of = orders.column("O_LINES").combine_chunks()
    lengths = pc.list_value_length(lines_of).to_numpy(zero_copy_only=False)
    if not np.array_equal(lengths, facts["line_counts"]):
        wrong.append(f"{int((lengths != facts['line_counts']).sum())} "
                     "orders whose list is not as long as the count drawn")
    lines = pa.Table.from_struct_array(pc.list_flatten(lines_of))
    if lines.num_rows != facts["line_rows"]:
        wrong.append(f"{lines.num_rows} lines != {facts['line_rows']} drawn")
    wrong += _sum_failures(table, facts["sums"], CUSTOMER_SUMS, "")
    wrong += _sum_failures(orders, facts["sums"], ORDER_SUMS, "C_ORDERS.")
    wrong += _sum_failures(lines, facts["sums"], LINE_SUMS,
                           "C_ORDERS.O_LINES.")
    comment = pc.utf8_rpad(table.column("C_COMMENT"),
                           COMMENT_BYTES).combine_chunks()
    plain = pc.fill_null(pc.equal(pc.binary_length(comment),
                                  COMMENT_BYTES), False)
    kept = plain.to_numpy(zero_copy_only=False)
    held = comment.filter(plain)
    text = np.frombuffer(held.buffers()[2], dtype=np.uint8,
                         count=len(held) * COMMENT_BYTES)
    differ = int((~kept).sum()) + int(
        (digest(text.reshape(-1, COMMENT_BYTES))
         != facts["comment_digests"][kept]).sum())
    if differ:
        wrong.append(f"{differ} customers whose C_COMMENT, the field behind "
                     "the array, is not the text drawn")
    if wrong:
        return wrong
    return _reference_failures(table, facts)
