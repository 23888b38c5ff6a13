"""TPC-H ORDERS with each order's 1 to 7 LINEITEM rows (Standard
Specification rev 3, clauses 1.4.1 and 4.2.3) as ONE variable-length
mainframe record: the order's header, a count, the lines as
`OCCURS 1 TO 7 TIMES DEPENDING ON` the count, and the order's free text
BEHIND them, so that every byte of the comment lies where the count puts
it. RDW-framed (little-endian, as upstream's generators write), EBCDIC.

Frozen with the benchmark: nothing here imports the program. The lines
are `tpch_lineitem`'s draws (its `draw` and its `encode`, less
`L-ORDERKEY`); the order's own columns are drawn here. The generator
returns what it drew: orders, lines an order, integer sums of every
numeric column of header and lines, and for every order its key, its
count and a digest of its `O-COMMENT`; `check_table` holds a decoded
table to them, and a shift wrong by one element cannot pass it (the
comment of every row is compared).

The record, 283 B (one line) to 1,153 B (seven), plus its 4 B RDW:

    O-ORDERKEY, O-CUSTKEY            PIC S9(9) COMP          8
    O-ORDERSTATUS                    PIC X                   1
    O-TOTALPRICE                     PIC S9(10)V99 COMP-3    7
    O-ORDERDATE                      PIC 9(8), yyyymmdd      8
    O-ORDERPRIORITY, O-CLERK         PIC X(15) each         30
    O-SHIPPRIORITY                   PIC S9(9) COMP          4
    O-LINE-COUNT                     PIC 9(1)                1
    O-LINES OCCURS 1 TO 7 DEPENDING ON O-LINE-COUNT        145 an element
    O-COMMENT                        PIC X(79), space padded 79
"""
import decimal

import numpy as np

from . import tpch_lineitem as lineitem
from .ebcdic import ENCODE_LUT, encode_comp_be, sample_indices

COPYBOOK = """
       01  ORDERS.
           05  O-ORDERKEY       PIC S9(9) COMP.
           05  O-CUSTKEY        PIC S9(9) COMP.
           05  O-ORDERSTATUS    PIC X.
           05  O-TOTALPRICE     PIC S9(10)V99 COMP-3.
           05  O-ORDERDATE      PIC 9(8).
           05  O-ORDERPRIORITY  PIC X(15).
           05  O-CLERK          PIC X(15).
           05  O-SHIPPRIORITY   PIC S9(9) COMP.
           05  O-LINE-COUNT     PIC 9(1).
           05  O-LINES OCCURS 1 TO 7 TIMES DEPENDING ON O-LINE-COUNT.
               10  L-PARTKEY        PIC S9(9) COMP.
               10  L-SUPPKEY        PIC S9(9) COMP.
               10  L-LINENUMBER     PIC S9(9) COMP.
               10  L-QUANTITY       PIC S9(10)V99 COMP-3.
               10  L-EXTENDEDPRICE  PIC S9(10)V99 COMP-3.
               10  L-DISCOUNT       PIC S9(10)V99 COMP-3.
               10  L-TAX            PIC S9(10)V99 COMP-3.
               10  L-RETURNFLAG     PIC X.
               10  L-LINESTATUS     PIC X.
               10  L-SHIPDATE       PIC 9(8).
               10  L-COMMITDATE     PIC 9(8).
               10  L-RECEIPTDATE    PIC 9(8).
               10  L-SHIPINSTRUCT   PIC X(25).
               10  L-SHIPMODE       PIC X(10).
               10  L-COMMENT        PIC X(44).
           05  O-COMMENT        PIC X(79).
"""
HEADER_BYTES = 59
LINE_BYTES = lineitem.RECORD_SIZE - 4       # the line less L-ORDERKEY
COMMENT_BYTES = 79
MIN_LINES, MAX_LINES = 1, 7
MIN_RECORD = HEADER_BYTES + MIN_LINES * LINE_BYTES + COMMENT_BYTES
MAX_RECORD = HEADER_BYTES + MAX_LINES * LINE_BYTES + COMMENT_BYTES
# the RDW and a record of four lines, the mean of a uniform 1..7
MEAN_RECORD_BYTES = 4 + HEADER_BYTES + 4 * LINE_BYTES + COMMENT_BYTES
CUSTOMERS = 150_000                         # scale factor 1 (clause 4.2.3)
CLERKS = 1000
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
COMMENT_SLOTS, SLOT_BYTES = 7, 11           # up to seven seeded words
# the digest of a comment: its 79 characters as digits of this base,
# modulo 2^64
DIGEST_BASE = np.uint64(1099511628211)
# the sums `check_table` holds a table to: (fact, column, scale or None
# for an integer column)
HEADER_SUMS = (("orderkey", "O_ORDERKEY", None),
               ("custkey", "O_CUSTKEY", None),
               ("totalprice", "O_TOTALPRICE", 2),
               ("orderdate", "O_ORDERDATE", None),
               ("shippriority", "O_SHIPPRIORITY", None),
               ("lines", "O_LINE_COUNT", None))
LINE_SUMS = (("partkey", "L_PARTKEY", None), ("suppkey", "L_SUPPKEY", None),
             ("linenumber", "L_LINENUMBER", None),
             ("quantity", "L_QUANTITY", 2), ("price", "L_EXTENDEDPRICE", 2),
             ("discount", "L_DISCOUNT", 2), ("tax", "L_TAX", 2),
             ("shipdate", "L_SHIPDATE", None),
             ("commitdate", "L_COMMITDATE", None),
             ("receiptdate", "L_RECEIPTDATE", None))
PER_ORDER = ("keys", "counts", "comment_digests")


def digest(text: np.ndarray) -> np.ndarray:
    """[n] uint64 digests of [n, 79] ASCII comments, space padded."""
    powers = DIGEST_BASE ** np.arange(COMMENT_BYTES, dtype=np.uint64)
    out = np.empty(len(text), dtype=np.uint64)
    for start in range(0, len(text), 1 << 16):
        block = text[start:start + (1 << 16)].astype(np.uint64)
        out[start:start + (1 << 16)] = (block * powers).sum(
            axis=1, dtype=np.uint64)
    return out


def draw(orders: int, seed: int) -> dict:
    """The integers of `orders` orders and of their lines. The lines are
    `tpch_lineitem.draw`'s over enough rows for `orders` whole orders,
    cut to them; its first two draws (lines an order, the order's day)
    are replayed here for the order's own date, the rest of the order's
    columns come from a stream of their own."""
    upper = orders * MAX_LINES
    d = lineitem.draw(upper, seed)
    rng = np.random.default_rng(seed)
    counts = rng.integers(MIN_LINES, MAX_LINES + 1, size=upper)
    starts = np.cumsum(counts) - counts
    drawn_orders = int(np.searchsorted(starts, upper))
    order_day = rng.integers(0, lineitem.ORDER_DAYS, size=drawn_orders)
    counts, starts = counts[:orders], starts[:orders]
    total = int(counts.sum())
    lines = {key: value[:total] for key, value in d.items()}
    order_of = np.repeat(np.arange(orders), counts)
    orderdate = lineitem._yyyymmdd(order_day[:orders])
    if not (np.array_equal(lines["orderkey"], order_of + 1)
            and np.array_equal(lines["linenumber"],
                               np.arange(total) - starts[order_of] + 1)
            and (orderdate[order_of] < lines["shipdate"]).all()):
        raise RuntimeError("the replayed draws are not tpch_lineitem's")
    own = np.random.default_rng([seed, 0x0DE125])
    # extendedprice x (1 + tax) x (1 - discount), summed in millionths
    # of hundredths and rounded half up to hundredths
    charge = (lines["price"] * (100 + lines["tax"])
              * (100 - lines["discount"]))
    total_charge = np.add.reduceat(charge, starts)
    still_open = np.add.reduceat((lines["linestatus"] == "O").astype(
        np.int64), starts)
    return {
        "lines": lines, "counts": counts, "starts": starts,
        "orderkey": np.arange(1, orders + 1),
        "custkey": own.integers(1, CUSTOMERS + 1, size=orders),
        "status": np.where(still_open == 0, "F",
                           np.where(still_open == counts, "O", "P")),
        "totalprice": (total_charge + 5000) // 10000,
        "orderdate": orderdate,
        "priority": own.integers(0, len(PRIORITIES), size=orders),
        "clerk": own.integers(1, CLERKS + 1, size=orders),
        "shippriority": np.zeros(orders, dtype=np.int64),
        "words": own.integers(0, len(lineitem.WORDS),
                              size=(orders, COMMENT_SLOTS)),
        "word_count": own.integers(1, COMMENT_SLOTS + 1, size=orders),
    }


def comments(d: dict) -> np.ndarray:
    """[n, 79] ASCII comments: the order's first `word_count` seeded
    words in 11 B slots, space padded."""
    words = np.full((len(lineitem.WORDS), SLOT_BYTES), 0x20, dtype=np.uint8)
    for i, word in enumerate(lineitem.WORDS):
        words[i, :len(word)] = np.frombuffer(word.encode("ascii"), np.uint8)
    text = np.full((len(d["words"]), COMMENT_BYTES), 0x20, dtype=np.uint8)
    slots = words[d["words"]]
    slots[np.arange(COMMENT_SLOTS)[None, :] >= d["word_count"][:, None]] = 0x20
    text[:, :COMMENT_SLOTS * SLOT_BYTES] = slots.reshape(len(slots), -1)
    return text


def encode(d: dict) -> bytes:
    """The drawn orders as RDW-framed record bytes, in order."""
    orders = len(d["counts"])
    clerks = np.char.add("Clerk#", np.char.zfill(
        d["clerk"].astype(str), 9)).tolist()
    header = np.concatenate([
        encode_comp_be(d["orderkey"], 4), encode_comp_be(d["custkey"], 4),
        ENCODE_LUT[d["status"].astype("S1").view(np.uint8)][:, None],
        lineitem._comp3(d["totalprice"]), lineitem._display(d["orderdate"]),
        lineitem._ebcdic(PRIORITIES, 15)[d["priority"]],
        lineitem._ebcdic(clerks, 15), encode_comp_be(d["shippriority"], 4),
        lineitem._display(d["counts"], 1)], axis=1)
    if header.shape[1] != HEADER_BYTES:
        raise RuntimeError(f"an order's header is {header.shape[1]} B")
    lines = lineitem.encode(d["lines"])[:, 4:]
    comment = ENCODE_LUT[comments(d)]
    lengths = HEADER_BYTES + d["counts"] * LINE_BYTES + COMMENT_BYTES
    rdw = np.zeros((orders, 4), dtype=np.uint8)
    rdw[:, 2], rdw[:, 3] = lengths & 0xFF, lengths >> 8
    records = [None] * orders
    for count in range(MIN_LINES, MAX_LINES + 1):
        # the orders of one count have one length: a matrix of them
        idx = np.flatnonzero(d["counts"] == count)
        if not len(idx):
            continue
        of_lines = (d["starts"][idx][:, None]
                    + np.arange(count)[None, :]).reshape(-1)
        rows = np.concatenate(
            [rdw[idx], header[idx],
             lines[of_lines].reshape(len(idx), count * LINE_BYTES),
             comment[idx]], axis=1)
        for k, i in enumerate(idx.tolist()):
            records[i] = rows[k].tobytes()
    return b"".join(records)


def generate(records: int, seed: int):
    """(the bytes of `records` orders drawn from `seed`, what is known of
    them without the program)."""
    d = draw(records, seed)
    data = encode(d)
    lines = d["lines"]
    facts = {"records": records, "bytes": len(data),
             "line_rows": int(d["counts"].sum()),
             "keys": d["orderkey"].astype(np.int32),
             "counts": d["counts"].astype(np.uint8),
             "comment_digests": digest(comments(d)),
             "sums": {**{key: int(d[key if key != "lines" else "counts"]
                                  .sum()) for key, _, _ in HEADER_SUMS},
                      **{key: int(lines[key].sum())
                         for key, _, _ in LINE_SUMS}}}
    return data, facts


def records_for(target_bytes: int) -> int:
    """Orders that come to about `target_bytes` at the mean record size."""
    return max(1, int(target_bytes / MEAN_RECORD_BYTES))


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order."""
    merged = {key: sum(p[key] for p in parts)
              for key in ("records", "bytes", "line_rows")}
    for key in PER_ORDER:
        merged[key] = np.concatenate([p[key] for p in parts])
    merged["sums"] = {key: sum(p["sums"][key] for p in parts)
                      for key in parts[0]["sums"]}
    return merged


def record_offsets(raw: np.ndarray) -> np.ndarray:
    """Where each record's RDW starts, by walking the headers."""
    offsets = []
    pos, end = 0, len(raw)
    while pos < end:
        offsets.append(pos)
        pos += 4 + (int(raw[pos + 2]) | (int(raw[pos + 3]) << 8))
    return np.asarray(offsets, dtype=np.int64)


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a seeded sample of whole records, RDW and all, into
    `out_path`; returns their record indices."""
    raw = np.memmap(path, dtype=np.uint8, mode="r")
    offsets = record_offsets(raw)
    ends = np.append(offsets[1:], len(raw))
    idx = sample_indices(len(offsets), size, seed)
    with open(out_path, "wb") as f:
        for i in idx.tolist():
            f.write(raw[offsets[i]:ends[i]].tobytes())
    return idx


def _decimal(value: int, scale: int) -> decimal.Decimal:
    """`value` units of 10^-`scale`, every digit kept."""
    return decimal.Decimal(value).scaleb(-scale)


def _sum_failures(table, sums: dict, which, what: str) -> list:
    import pyarrow.compute as pc

    wrong = []
    for key, column, scale in which:
        values = table.column(column)
        if scale is None:
            values = pc.cast(values, "int64")
        total = pc.sum(values).as_py()
        drawn = sums[key] if scale is None else _decimal(sums[key], scale)
        if total != drawn:
            wrong.append(f"sum({what}{column}) {total} != {drawn} drawn")
    return wrong


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the
    program: the orders and their keys, each order's list as long as its
    count drawn, the sums of every numeric column of the headers and of
    the flattened lines, and the field BEHIND the array, `O-COMMENT`, of
    every row by its digest. Returns the list of what does not hold."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if table.num_rows != facts["records"]:
        return [f"rows {table.num_rows} != {facts['records']} written"]
    wrong = []
    keys = table.column("O_ORDERKEY").to_numpy()
    if not np.array_equal(keys, facts["keys"]):
        wrong.append(f"{int((keys != facts['keys']).sum())} orders with "
                     "another O_ORDERKEY than drawn")
    lists = table.column("O_LINES").combine_chunks()
    lengths = pc.list_value_length(lists).to_numpy(zero_copy_only=False)
    if not np.array_equal(lengths, facts["counts"]):
        wrong.append(f"{int((lengths != facts['counts']).sum())} orders "
                     "whose list is not as long as the count drawn")
    lines = pa.Table.from_struct_array(pc.list_flatten(lists))
    if lines.num_rows != facts["line_rows"]:
        wrong.append(f"{lines.num_rows} lines != {facts['line_rows']} drawn")
    wrong += _sum_failures(table, facts["sums"], HEADER_SUMS, "")
    wrong += _sum_failures(lines, facts["sums"], LINE_SUMS, "O_LINES.")
    comment = pc.utf8_rpad(table.column("O_COMMENT"),
                           COMMENT_BYTES).combine_chunks()
    # a text of another length in bytes (null, or characters past ASCII)
    # is not one that was drawn
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
        wrong.append(f"{differ} orders whose O_COMMENT, the field behind "
                     "the array, is not the text drawn")
    return wrong
