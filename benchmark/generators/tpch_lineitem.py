"""TPC-H LINEITEM (Standard Specification rev 3, clause 1.4.1) as a
fixed-length EBCDIC mainframe table, populated as clause 4.2.3
prescribes, with the answers of Q6 (2.4.6) and Q1 (2.4.1) under their
validation parameters computed from the integers drawn.

Frozen with the benchmark: nothing here imports the program. The draws
are numpy's from the seed, not dbgen's generator, so SF 1's published
answers do not apply and `query_answers` gives this file's own. Order
keys are dense within a generated chunk and the comment is seeded words
in four slots; no query of the suite's two scans reads either.

The record, 149 B, 16 fields:

    L-ORDERKEY, L-PARTKEY, L-SUPPKEY, L-LINENUMBER      PIC S9(9) COMP
    L-QUANTITY, L-EXTENDEDPRICE, L-DISCOUNT, L-TAX      PIC S9(10)V99 COMP-3
    L-RETURNFLAG, L-LINESTATUS                          PIC X
    L-SHIPDATE, L-COMMITDATE, L-RECEIPTDATE             PIC 9(8), yyyymmdd
    L-SHIPINSTRUCT X(25), L-SHIPMODE X(10), L-COMMENT X(44), space padded
"""
import decimal

import numpy as np

from .ebcdic import ENCODE_LUT, encode_comp_be, sample_indices

COPYBOOK = """
       01  LINEITEM.
           05  L-ORDERKEY       PIC S9(9) COMP.
           05  L-PARTKEY        PIC S9(9) COMP.
           05  L-SUPPKEY        PIC S9(9) COMP.
           05  L-LINENUMBER     PIC S9(9) COMP.
           05  L-QUANTITY       PIC S9(10)V99 COMP-3.
           05  L-EXTENDEDPRICE  PIC S9(10)V99 COMP-3.
           05  L-DISCOUNT       PIC S9(10)V99 COMP-3.
           05  L-TAX            PIC S9(10)V99 COMP-3.
           05  L-RETURNFLAG     PIC X.
           05  L-LINESTATUS     PIC X.
           05  L-SHIPDATE       PIC 9(8).
           05  L-COMMITDATE     PIC 9(8).
           05  L-RECEIPTDATE    PIC 9(8).
           05  L-SHIPINSTRUCT   PIC X(25).
           05  L-SHIPMODE       PIC X(10).
           05  L-COMMENT        PIC X(44).
"""
RECORD_SIZE = 149
# scale factor 1 (clause 4.2.3): parts and suppliers a line may name
PARTS = 200_000
SUPPLIERS = 10_000
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
WORDS = ("furiously", "sly", "careful", "blithe", "quick", "fluffy",
         "slow", "quiet", "ruthless", "thin", "close", "dogged", "daring",
         "brave", "stealthy", "permanent", "enticing", "idle", "busy",
         "regular", "final", "ironic", "even", "bold", "silent", "pending",
         "express", "special", "unusual", "deposits", "requests", "packages")
# order dates: 1992-01-01 to the end date 1998-12-31 less 151 days
START = np.datetime64("1992-01-01")
ORDER_DAYS = int((np.datetime64("1998-12-31") - 151 - START).astype(int)) + 1
CURRENT = np.datetime64("1995-06-17")
# the validation parameters of Q6 (2.4.6.3) and Q1 (2.4.1.3)
Q6_FROM, Q6_TO = 19940101, 19950101
Q6_DISCOUNT = (5, 7)            # 0.06 +- 0.01, in hundredths
Q6_QUANTITY = 2400              # < 24, in hundredths
Q1_UNTIL = 19980902             # 1998-12-01 less 90 days


def _ebcdic(text_rows, width: int) -> np.ndarray:
    """Rows of ASCII text to [n, width] EBCDIC, space padded."""
    table = np.full((len(text_rows), width), 0x40, dtype=np.uint8)
    for i, text in enumerate(text_rows):
        raw = np.frombuffer(text.encode("ascii"), dtype=np.uint8)
        table[i, :len(raw)] = ENCODE_LUT[raw]
    return table


def _comp3(values: np.ndarray, width: int = 7) -> np.ndarray:
    """Non-negative [n] ints to [n, width] packed decimal, sign nibble C."""
    nibbles = np.empty((len(values), width * 2), dtype=np.uint8)
    nibbles[:, -1] = 0x0C
    v = values.astype(np.int64)
    for pos in range(width * 2 - 2, -1, -1):
        nibbles[:, pos] = v % 10
        v = v // 10
    return (nibbles[:, 0::2] << 4) | nibbles[:, 1::2]


def _yyyymmdd(days: np.ndarray) -> np.ndarray:
    """Days after START to yyyymmdd integers."""
    dates = START + days.astype("timedelta64[D]")
    months = dates.astype("datetime64[M]")
    year = dates.astype("datetime64[Y]").astype(np.int64) + 1970
    month = months.astype(np.int64) % 12 + 1
    day = (dates - months).astype(np.int64) + 1
    return year * 10000 + month * 100 + day


def _display(values: np.ndarray, width: int = 8) -> np.ndarray:
    """[n] ints to [n, width] EBCDIC digits."""
    out = np.empty((len(values), width), dtype=np.uint8)
    v = values.astype(np.int64)
    for pos in range(width - 1, -1, -1):
        out[:, pos] = 0xF0 + v % 10
        v = v // 10
    return out


def draw(records: int, seed: int) -> dict:
    """The integers of `records` lines: orders of one to seven lines
    until the rows are full (the last order may be cut short)."""
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, size=records)          # lines an order
    starts = np.cumsum(lines) - lines
    orders = int(np.searchsorted(starts, records))
    lines, starts = lines[:orders], starts[:orders]
    order_of = np.repeat(np.arange(orders), lines)[:records]
    linenumber = np.arange(records) - starts[order_of] + 1
    order_day = rng.integers(0, ORDER_DAYS, size=orders)[order_of]
    partkey = rng.integers(1, PARTS + 1, size=records)
    quantity = rng.integers(1, 51, size=records)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    ship_day = order_day + rng.integers(1, 122, size=records)
    receipt_day = ship_day + rng.integers(1, 31, size=records)
    current = int((CURRENT - START).astype(int))
    returned = np.where(rng.integers(0, 2, size=records) == 1, "R", "A")
    return {
        "orderkey": order_of + 1, "partkey": partkey,
        "suppkey": rng.integers(1, SUPPLIERS + 1, size=records),
        "linenumber": linenumber,
        "quantity": quantity * 100, "price": quantity * retail,
        "discount": rng.integers(0, 11, size=records),
        "tax": rng.integers(0, 9, size=records),
        "returnflag": np.where(receipt_day <= current, returned, "N"),
        "linestatus": np.where(ship_day > current, "O", "F"),
        "shipdate": _yyyymmdd(ship_day),
        "commitdate": _yyyymmdd(order_day
                                + rng.integers(30, 91, size=records)),
        "receiptdate": _yyyymmdd(receipt_day),
        "instruct": rng.integers(0, len(INSTRUCTIONS), size=records),
        "mode": rng.integers(0, len(MODES), size=records),
        "words": rng.integers(0, len(WORDS), size=(records, 4)),
    }


def answers(d: dict) -> dict:
    """Q6's and Q1's partial answers over the drawn integers `d`, in
    hundredths and their products: plain numpy int64 over one chunk (a
    chunk's largest sum is under 10^17), Python integers from there."""
    q6 = ((d["shipdate"] >= Q6_FROM) & (d["shipdate"] < Q6_TO)
          & (d["discount"] >= Q6_DISCOUNT[0])
          & (d["discount"] <= Q6_DISCOUNT[1])
          & (d["quantity"] < Q6_QUANTITY))
    out = {"q6": {"rows": int(q6.sum()),
                  "revenue": int((d["price"] * d["discount"])[q6].sum())},
           "q1": {}}
    q1 = d["shipdate"] <= Q1_UNTIL
    disc_price = d["price"] * (100 - d["discount"])
    charge = disc_price * (100 + d["tax"])
    for flag in np.unique(d["returnflag"][q1]):
        for status in np.unique(d["linestatus"][q1]):
            rows = q1 & (d["returnflag"] == flag) & (d["linestatus"]
                                                     == status)
            if rows.any():
                out["q1"][f"{flag}{status}"] = {
                    "rows": int(rows.sum()),
                    "quantity": int(d["quantity"][rows].sum()),
                    "price": int(d["price"][rows].sum()),
                    "disc_price": int(disc_price[rows].sum()),
                    "charge": int(charge[rows].sum()),
                    "discount": int(d["discount"][rows].sum())}
    return out


def encode(d: dict) -> np.ndarray:
    """The drawn integers as [n, RECORD_SIZE] record bytes."""
    def flag(letters):
        return ENCODE_LUT[letters.astype("S1").view(np.uint8)][:, None]

    words = _ebcdic(WORDS, 11)
    out = np.concatenate(
        [encode_comp_be(d[k], 4) for k in ("orderkey", "partkey", "suppkey",
                                           "linenumber")]
        + [_comp3(d[k]) for k in ("quantity", "price", "discount", "tax")]
        + [flag(d["returnflag"]), flag(d["linestatus"])]
        + [_display(d[k]) for k in ("shipdate", "commitdate", "receiptdate")]
        + [_ebcdic(INSTRUCTIONS, 25)[d["instruct"]],
           _ebcdic(MODES, 10)[d["mode"]],
           words[d["words"]].reshape(len(d["words"]), 44)], axis=1)
    if out.shape[1] != RECORD_SIZE:
        raise RuntimeError(f"a lineitem record is {out.shape[1]} B, "
                           f"not {RECORD_SIZE}")
    return out


def generate(records: int, seed: int):
    """(the bytes of `records` lines drawn from `seed`, what is known of
    them without the program)."""
    d = draw(records, seed)
    data = encode(d).tobytes()
    facts = {"records": records, "bytes": len(data),
             "price": int(d["price"].sum()),
             "linenumber": int(d["linenumber"].sum()),
             "shipdate": int(d["shipdate"].sum()),
             "open": int((d["linestatus"] == "O").sum()),
             "answers": answers(d)}
    return data, facts


def records_for(target_bytes: int) -> int:
    """Whole records that fit `target_bytes`: a 64 MiB chunk is then one
    read chunk of the program exactly, with no ragged tail."""
    return max(1, target_bytes // RECORD_SIZE)


def merge_facts(parts: list) -> dict:
    """Facts of a file made of several generated chunks, in order."""
    merged = {key: sum(p[key] for p in parts)
              for key in ("records", "bytes", "price", "linenumber",
                          "shipdate", "open")}
    q6 = {key: sum(p["answers"]["q6"][key] for p in parts)
          for key in ("rows", "revenue")}
    q1: dict = {}
    for p in parts:
        for group, sums in p["answers"]["q1"].items():
            held = q1.setdefault(group, dict.fromkeys(sums, 0))
            for key, value in sums.items():
                held[key] += value
    merged["answers"] = {"q6": q6, "q1": q1}
    return merged


def _decimal(value: int, scale: int) -> decimal.Decimal:
    """`value` units of 10^-`scale`, every digit kept."""
    return decimal.Decimal((0, tuple(int(d) for d in str(value)), -scale))


def query_answers(facts: dict) -> dict:
    """What `dataset(...).aggregate()` returns for the configuration's
    `queries`, from the drawn integers alone: Q6's dict, and Q1's rows
    (`pyarrow.Table.to_pylist()`), ascending by key. Sums at the sum of
    their factors' scales; an average is its sum over its count in
    `decimal`'s default context."""
    q6 = facts["answers"]["q6"]
    rows = []
    for group, s in sorted(facts["answers"]["q1"].items()):
        n = decimal.Decimal(s["rows"])
        rows.append({
            "L_RETURNFLAG": group[0], "L_LINESTATUS": group[1],
            "sum:L_QUANTITY": _decimal(s["quantity"], 2),
            "sum:L_EXTENDEDPRICE": _decimal(s["price"], 2),
            "sum:L_EXTENDEDPRICE*(1-L_DISCOUNT)":
                _decimal(s["disc_price"], 4),
            "sum:L_EXTENDEDPRICE*(1-L_DISCOUNT)*(1+L_TAX)":
                _decimal(s["charge"], 6),
            "avg:L_QUANTITY": _decimal(s["quantity"], 2) / n,
            "avg:L_EXTENDEDPRICE": _decimal(s["price"], 2) / n,
            "avg:L_DISCOUNT": _decimal(s["discount"], 2) / n,
            "count": s["rows"]})
    return {"q6": {"sum:L_EXTENDEDPRICE*L_DISCOUNT":
                   _decimal(q6["revenue"], 4) if q6["rows"] else None},
            "q1": rows}


def sample(path: str, out_path: str, size: int, seed: int) -> np.ndarray:
    """Copy a seeded sample of whole records into `out_path`; returns
    their record indices."""
    raw = np.memmap(path, dtype=np.uint8, mode="r").reshape(-1, RECORD_SIZE)
    idx = sample_indices(raw.shape[0], size, seed)
    with open(out_path, "wb") as f:
        f.write(raw[idx].tobytes())
    return idx


def check_table(table, facts: dict) -> list:
    """What this generator knows of the decoded table without the
    program: the rows, the sums of the prices, line numbers and ship
    dates as drawn, and how many lines are still open. Returns the list
    of what does not hold."""
    import pyarrow.compute as pc

    if table.num_rows != facts["records"]:
        return [f"rows {table.num_rows} != {facts['records']} written"]
    wrong = []
    price = pc.sum(table.column("L_EXTENDEDPRICE")).as_py()
    if price != _decimal(facts["price"], 2):
        wrong.append(f"sum(L_EXTENDEDPRICE) {price} != "
                     f"{_decimal(facts['price'], 2)} drawn")
    for column, key in (("L_LINENUMBER", "linenumber"),
                        ("L_SHIPDATE", "shipdate")):
        total = pc.sum(pc.cast(table.column(column), "int64")).as_py()
        if total != facts[key]:
            wrong.append(f"sum({column}) {total} != {facts[key]} drawn")
    still_open = pc.sum(pc.equal(table.column("L_LINESTATUS"), "O")).as_py()
    if still_open != facts["open"]:
        wrong.append(f"{still_open} lines with L_LINESTATUS 'O' != "
                     f"{facts['open']} drawn")
    return wrong


def reference_answers(table) -> dict:
    """Q6 and Q1 in plain pyarrow.compute over a decoded table, in the
    shape of `query_answers`: the reference the program's answers are
    held to, written from the specification's SQL and nothing of the
    program. Products are made in decimal256, so nothing rounds."""
    import pyarrow as pa
    import pyarrow.compute as pc

    def wide(name, t):
        col = t.column(name)
        return pc.cast(col, pa.decimal256(col.type.precision,
                                          col.type.scale))

    one = pa.scalar(decimal.Decimal(1), pa.decimal256(1, 0))
    ship, disc = pc.field("L_SHIPDATE"), pc.field("L_DISCOUNT")
    t6 = table.filter(
        (ship >= Q6_FROM) & (ship < Q6_TO)
        & (disc >= decimal.Decimal("0.05")) & (disc <= decimal.Decimal("0.07"))
        & (pc.field("L_QUANTITY") < decimal.Decimal(24)))
    revenue = pc.sum(pc.multiply(wide("L_EXTENDEDPRICE", t6),
                                 wide("L_DISCOUNT", t6))).as_py()
    t1 = table.filter(ship <= Q1_UNTIL)
    disc_price = pc.multiply(wide("L_EXTENDEDPRICE", t1),
                             pc.subtract(one, wide("L_DISCOUNT", t1)))
    grouped = pa.table({
        "flag": t1.column("L_RETURNFLAG"), "status": t1.column("L_LINESTATUS"),
        "quantity": wide("L_QUANTITY", t1), "price": wide("L_EXTENDEDPRICE", t1),
        "disc_price": disc_price,
        "charge": pc.multiply(disc_price, pc.add(one, wide("L_TAX", t1))),
        "discount": wide("L_DISCOUNT", t1),
    }).group_by(["flag", "status"], use_threads=False).aggregate(
        [("quantity", "sum"), ("price", "sum"), ("disc_price", "sum"),
         ("charge", "sum"), ("discount", "sum"), ([], "count_all")])
    rows = []
    for g in sorted(grouped.to_pylist(),
                    key=lambda g: (g["flag"], g["status"])):
        n = decimal.Decimal(g["count_all"])
        rows.append({
            "L_RETURNFLAG": g["flag"], "L_LINESTATUS": g["status"],
            "sum:L_QUANTITY": g["quantity_sum"],
            "sum:L_EXTENDEDPRICE": g["price_sum"],
            "sum:L_EXTENDEDPRICE*(1-L_DISCOUNT)": g["disc_price_sum"],
            "sum:L_EXTENDEDPRICE*(1-L_DISCOUNT)*(1+L_TAX)": g["charge_sum"],
            "avg:L_QUANTITY": g["quantity_sum"] / n,
            "avg:L_EXTENDEDPRICE": g["price_sum"] / n,
            "avg:L_DISCOUNT": g["discount_sum"] / n,
            "count": g["count_all"]})
    return {"q6": {"sum:L_EXTENDEDPRICE*L_DISCOUNT": revenue}, "q1": rows}
