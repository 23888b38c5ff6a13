"""The frozen generator of TPC-H customers with their orders and lines
nested two levels deep (`tpch_customers_nested`): the record's widths, the
counts (none for a key that is a multiple of three, about 15 for the
others, at most 40; lines 1..7), determinism by seed, facts that merge,
the plain reference, and a `check_table` that catches a spoiled line, an
order list one element short and a `C-COMMENT` read one order off."""
import json
import os

import numpy as np
import pytest

import benchmark_testing  # noqa: F401  (puts the repo on sys.path)
from benchmark.generators import tpch_customers_nested as gen
from benchmark.generators import tpch_orders_nested

pa = pytest.importorskip("pyarrow")

CONFIG = os.path.join(benchmark_testing.REPO, "benchmark", "configs",
                      "tpch_customers_nested.json")
OPTIONS = dict(copybook_contents=gen.COPYBOOK, is_record_sequence="true",
               variable_size_occurs="true",
               schema_retention_policy="collapse_root",
               ebcdic_code_page="cp037")


def decoded(data: bytes, tmp_path, backend: str):
    from cobrix_tpu import read_cobol

    path = tmp_path / f"customers.{backend}.dat"
    path.write_bytes(data)
    return read_cobol(str(path), backend=backend, **OPTIONS).to_arrow()


def records_of(data: bytes) -> list:
    raw = np.frombuffer(data, dtype=np.uint8)
    offsets = tpch_orders_nested.record_offsets(raw)
    ends = np.append(offsets[1:], len(raw))
    return [data[o + 4:e] for o, e in zip(offsets.tolist(), ends.tolist())]


def test_the_record_is_the_configurations():
    from cobrix_tpu import parse_copybook

    with open(CONFIG) as f:
        config = json.load(f)
    copybook = parse_copybook(gen.COPYBOOK)
    fields = copybook.ast.children[0].children
    widths = [st.binary_properties.data_size for st in fields]
    assert widths == [4, 25, 40, 4, 15, 7, 10, 2, 1149, 117]
    assert sum(widths[:8]) == gen.HEADER_BYTES == 107
    orders = fields[8]
    assert (orders.array_min_size, orders.array_max_size,
            orders.depending_on) == (0, 40, "C_ORDER_COUNT")
    element = [st.binary_properties.data_size for st in orders.children]
    assert element == [4, 1, 7, 8, 15, 15, 4, 1, 145, 79]
    lines = orders.children[8]
    assert (lines.array_min_size, lines.array_max_size,
            lines.depending_on) == (1, 7, "O_LINE_COUNT")
    assert gen.MIN_RECORD == 224
    assert copybook.record_size == 224 + 40 * 1149 == 46184
    assert config["record_bytes"] == {"min": 224, "max": 46184,
                                      "mean": 7364, "rdw": 4}
    assert gen.MEAN_RECORD_BYTES == 7364 + 4
    assert config["reduced"] == ["rows"]
    assert config["source_scale"]["customers"] == 150_000
    assert len(config["guarantees"]) == 5 and len(config["source"]) <= 200
    assert config["reader_options"] == {
        "backend": "pallas", "is_record_sequence": "true",
        "variable_size_occurs": "true",
        "schema_retention_policy": "collapse_root",
        "ebcdic_code_page": "cp037"}
    full = config["full"]
    customers = (full["file_bytes"] // full["generate_chunk_bytes"]
                 * gen.records_for(full["generate_chunk_bytes"]))
    assert abs(customers - config["rows"]) < 100


def test_the_route_reads_it_by_element_rows():
    from cobrix_tpu.explain import explain

    plan = explain(**dict(OPTIONS, backend="pallas")).plan
    assert plan["variable_occurs"] == "elements"
    assert plan["variable_regions"] == [
        "C_ORDERS[0..40]x1149B@107", "O_LINES[1..7]x145B@55 in C_ORDERS"]


def test_widths_counts_and_domains():
    data, facts = gen.generate(3000, 2 ** 31 + 11)
    records = records_of(data)
    assert len(records) == facts["records"] == 3000
    counts = facts["order_counts"].astype(np.int64)
    keys = facts["keys"].astype(np.int64)
    assert (counts[keys % 3 == 0] == 0).all()
    assert abs(counts[keys % 3 != 0].mean() - 15) < 0.5
    assert counts.max() <= 40
    lines = facts["line_counts"].astype(np.int64)
    assert len(lines) == facts["orders"] == int(counts.sum())
    assert lines.min() == 1 and lines.max() == 7
    first = np.cumsum(counts) - counts
    lengths = np.asarray([len(r) for r in records])
    per_order = 55 + 145 * lines + 79
    walked = 224 + np.asarray([per_order[f:f + c].sum()
                               for f, c in zip(first, counts)])
    assert np.array_equal(lengths, walked)
    assert len(data) == facts["bytes"] == int(lengths.sum()) + 4 * 3000
    assert facts["line_rows"] == int(lines.sum()) == facts["sums"]["lines"]
    d = gen.draw(3000, 2 ** 31 + 11)
    assert d["acctbal"].min() >= -99999 and d["acctbal"].max() <= 999999
    assert (d["acctbal"] < 0).any()
    assert d["nationkey"].max() < gen.NATIONS


def test_same_seed_same_bytes_and_facts_merge():
    a, facts_a = gen.generate(400, 2 ** 31 + 3)
    again, _ = gen.generate(400, 2 ** 31 + 3)
    b, facts_b = gen.generate(300, 2 ** 31 + 4)
    assert a == again and a != b[:len(a)]
    merged = gen.merge_facts([facts_a, facts_b])
    assert merged["records"] == 700
    assert merged["bytes"] == len(a) + len(b)
    assert merged["orders"] == facts_a["orders"] + facts_b["orders"]
    assert list(merged["keys"][398:402]) == [399, 400, 1, 2]
    assert merged["offsets"][400] == len(a)
    assert [c["seed"] for c in merged["chunks"]] == [2 ** 31 + 3,
                                                     2 ** 31 + 4]
    for key in facts_a["sums"]:
        assert merged["sums"][key] == (facts_a["sums"][key]
                                       + facts_b["sums"][key])


@pytest.mark.parametrize("backend", ["host", "numpy"])
def test_check_table_holds_the_decoded_table(tmp_path, backend):
    data, facts = gen.generate(150, 2 ** 31 + 9)
    assert gen.check_table(decoded(data, tmp_path, backend), facts) == []


def test_reference_rows_are_the_walks_trees(tmp_path):
    data, _ = gen.generate(60, 2 ** 31 + 5)
    table = decoded(data, tmp_path, "host")
    rows = [0, 1, 2, 17, 59]
    expected = gen.reference_rows(data, rows)
    assert table.take(rows).to_pylist() == [expected[r] for r in rows]
    assert expected[2]["C_ORDERS"] == []          # key 3: no order


def spoiled(data: bytes, facts: dict, how: str) -> bytes:
    """The bytes with one thing wrong in the first customer of two orders
    or more."""
    raw = bytearray(data)
    customer = int(np.flatnonzero(facts["order_counts"] >= 2)[0])
    at = int(facts["offsets"][customer]) + 4
    if how == "line":
        # the first order's first line's L-QUANTITY, one unit up
        raw[at + 107 + 55 + 12 + 6] ^= 0x10
    elif how == "short_list":
        count = int(facts["order_counts"][customer]) - 1
        raw[at + 105:at + 107] = bytes([0xF0 + count // 10,
                                        0xF0 + count % 10])
    return bytes(raw)


@pytest.mark.parametrize("how,complaint", [
    ("line", "sum(C_ORDERS.O_LINES.L_QUANTITY)"),
    ("short_list", "list is not as long as the count drawn"),
])
def test_check_table_catches_a_spoiled_file(tmp_path, how, complaint):
    data, facts = gen.generate(90, 2 ** 31 + 21)
    table = decoded(spoiled(data, facts, how), tmp_path, "numpy")
    wrong = gen.check_table(table, facts)
    assert any(complaint in w for w in wrong), wrong


def test_check_table_catches_a_comment_read_one_order_off(tmp_path):
    """What a count read wrong by one gives: every customer's comment
    read from where it would lie with its last order left out. The lists
    and every sum still hold; the comments do not."""
    data, facts = gen.generate(90, 2 ** 31 + 22)
    table = decoded(data, tmp_path, "numpy")
    shifted = []
    orders = table["C_ORDERS"].to_pylist()
    for record, order in zip(records_of(data), orders):
        at = len(record) - 117
        if order:
            at -= 55 + 145 * len(order[-1]["O_LINES"]) + 79
        shifted.append(bytes(record[at:at + 117]).decode("cp037").strip())
    spoiled_table = table.set_column(
        table.schema.get_field_index("C_COMMENT"), "C_COMMENT",
        pa.array(shifted).cast(table.schema.field("C_COMMENT").type))
    wrong = gen.check_table(spoiled_table, facts)
    assert len(wrong) == 1 and "behind the array" in wrong[0]
    # and a table a row short is refused before anything else
    assert "rows 89" in gen.check_table(table.slice(1), facts)[0]


def test_check_table_holds_a_tree_to_the_plain_reference(tmp_path):
    """A nested value that no count, sum or comment holds: two lines'
    L-SHIPMODE swapped between orders; `reference_rows` finds it."""
    data, facts = gen.generate(40, 2 ** 31 + 23)
    table = decoded(data, tmp_path, "numpy")
    rows = table.to_pylist()
    with_orders = [i for i, r in enumerate(rows) if len(r["C_ORDERS"]) >= 2]
    i = with_orders[0]
    a, b = rows[i]["C_ORDERS"][0], rows[i]["C_ORDERS"][1]
    a["O_LINES"][0]["L_SHIPMODE"], b["O_LINES"][0]["L_SHIPMODE"] = (
        b["O_LINES"][0]["L_SHIPMODE"] + "X", a["O_LINES"][0]["L_SHIPMODE"])
    swapped = pa.Table.from_pylist(rows, schema=table.schema)
    wrong = gen.check_table(swapped, facts)
    assert len(wrong) == 1 and "plain reference" in wrong[0], wrong
