"""The frozen generator of TPC-H orders with their lines nested
(`tpch_orders_nested`): the record's widths, the count uniform on 1..7,
determinism by seed, facts that merge, and a `check_table` that catches
a spoiled line, a list one element short and an `O-COMMENT` shifted by
one element (145 B)."""
import json
import os

import numpy as np
import pytest

import benchmark_testing  # noqa: F401  (puts the repo on sys.path)
from benchmark.generators import tpch_lineitem
from benchmark.generators import tpch_orders_nested as gen

pa = pytest.importorskip("pyarrow")

CONFIG = os.path.join(benchmark_testing.REPO, "benchmark", "configs",
                      "tpch_orders_nested.json")
OPTIONS = dict(copybook_contents=gen.COPYBOOK, is_record_sequence="true",
               variable_size_occurs="true",
               schema_retention_policy="collapse_root",
               ebcdic_code_page="cp037")


def decoded(data: bytes, tmp_path, backend: str):
    from cobrix_tpu import read_cobol

    path = tmp_path / f"orders.{backend}.dat"
    path.write_bytes(data)
    return read_cobol(str(path), backend=backend, **OPTIONS).to_arrow()


def records_of(data: bytes) -> list:
    raw = np.frombuffer(data, dtype=np.uint8)
    offsets = gen.record_offsets(raw)
    ends = np.append(offsets[1:], len(raw))
    return [data[o + 4:e] for o, e in zip(offsets.tolist(), ends.tolist())]


def test_the_record_is_the_configurations():
    from cobrix_tpu import parse_copybook
    from cobrix_tpu.plan.compiler import compile_plan

    with open(CONFIG) as f:
        config = json.load(f)
    copybook = parse_copybook(gen.COPYBOOK)
    header = copybook.ast.children[0].children
    widths = [st.binary_properties.data_size for st in header]
    assert widths == [4, 4, 1, 7, 8, 15, 15, 4, 1, 145, 79]
    assert sum(widths[:9]) == gen.HEADER_BYTES == 59
    line = header[9]
    assert (line.array_min_size, line.array_max_size,
            line.depending_on) == (1, 7, "O_LINE_COUNT")
    assert [st.binary_properties.data_size for st in line.children] == [
        4, 4, 4, 7, 7, 7, 7, 1, 1, 8, 8, 8, 25, 10, 44]
    assert line.binary_properties.data_size == gen.LINE_BYTES == (
        tpch_lineitem.RECORD_SIZE - 4)
    assert (gen.MIN_RECORD, gen.MAX_RECORD) == (283, 1153)
    assert config["record_bytes"] == {"min": 283, "max": 1153, "mean": 718,
                                      "rdw": 4}
    assert gen.MEAN_RECORD_BYTES == 718 + 4
    assert copybook.record_size == gen.MAX_RECORD
    # one region, its dependee beside it, the comment behind it
    plan = compile_plan(copybook, variable_size_occurs=True)
    (region,) = plan.regions
    assert (region.start, region.element_size, region.min_size,
            region.max_size, region.end) == (59, 145, 1, 7, 1074)
    assert plan.max_extent - region.end == gen.COMMENT_BYTES
    assert config["reduced"] == ["rows"]
    assert config["source_scale"]["orders"] == 1_500_000
    assert config["source_scale"]["lines"] == 6_001_215
    assert len(config["guarantees"]) == 5 and len(config["source"]) <= 200
    assert config["reader_options"] == {
        "backend": "pallas", "is_record_sequence": "true",
        "variable_size_occurs": "true",
        "schema_retention_policy": "collapse_root",
        "ebcdic_code_page": "cp037"}
    full = config["full"]
    orders = (full["file_bytes"] // full["generate_chunk_bytes"]
              * gen.records_for(full["generate_chunk_bytes"]))
    assert abs(orders - config["rows"]) < 1000


def test_widths_counts_and_domains():
    data, facts = gen.generate(3000, 2 ** 31 + 11)
    records = records_of(data)
    assert len(records) == facts["records"] == 3000
    lengths = np.asarray([len(r) for r in records])
    counts = facts["counts"].astype(np.int64)
    assert np.array_equal(lengths, 138 + 145 * counts)
    assert lengths.min() == 283 and lengths.max() == 1153
    assert len(data) == facts["bytes"] == int(lengths.sum()) + 4 * 3000
    # uniform on 1..7: every count about a seventh of the orders
    shares = np.bincount(counts, minlength=8)[1:] / 3000
    assert (abs(shares - 1 / 7) < 0.03).all()
    assert facts["line_rows"] == int(counts.sum()) == facts["sums"]["lines"]
    d = gen.draw(3000, 2 ** 31 + 11)
    assert set(np.unique(d["status"])) <= {"F", "O", "P"}
    assert d["custkey"].min() >= 1 and d["custkey"].max() <= gen.CUSTOMERS
    assert d["orderdate"].min() >= 19920101
    assert d["orderdate"].max() <= 19980802
    assert d["clerk"].min() >= 1 and d["clerk"].max() <= 1000
    assert np.array_equal(d["lines"]["linenumber"],
                          np.concatenate([np.arange(1, c + 1)
                                          for c in counts]))
    # an order's total is its lines' charges, rounded to hundredths
    first = slice(0, int(counts[0]))
    lines = d["lines"]
    charge = sum(int(p) * (100 + int(t)) * (100 - int(s))
                 for p, t, s in zip(lines["price"][first],
                                    lines["tax"][first],
                                    lines["discount"][first]))
    assert int(d["totalprice"][0]) == (charge + 5000) // 10000


def test_same_seed_same_bytes_and_facts_merge():
    a, facts_a = gen.generate(400, 2 ** 31 + 3)
    again, _ = gen.generate(400, 2 ** 31 + 3)
    b, facts_b = gen.generate(300, 2 ** 31 + 4)
    assert a == again and a != b[:len(a)]
    merged = gen.merge_facts([facts_a, facts_b])
    assert merged["records"] == 700
    assert merged["bytes"] == len(a) + len(b)
    assert merged["line_rows"] == facts_a["line_rows"] + facts_b["line_rows"]
    assert np.array_equal(merged["counts"], np.concatenate(
        [facts_a["counts"], facts_b["counts"]]))
    assert list(merged["keys"][398:402]) == [399, 400, 1, 2]
    for key in facts_a["sums"]:
        assert merged["sums"][key] == (facts_a["sums"][key]
                                       + facts_b["sums"][key])
    assert abs(gen.records_for(1 << 20) * gen.MEAN_RECORD_BYTES
               - (1 << 20)) < gen.MEAN_RECORD_BYTES


def test_sample_copies_whole_records(tmp_path):
    data, _ = gen.generate(200, 5)
    path, out = tmp_path / "orders.dat", tmp_path / "sample.dat"
    path.write_bytes(data)
    idx = gen.sample(str(path), str(out), 30, 2 ** 31 + 7)
    assert len(idx) == 30 and list(idx) == sorted(set(idx.tolist()))
    records = records_of(data)
    assert records_of(out.read_bytes()) == [records[i] for i in idx]


@pytest.mark.parametrize("backend", ["host", "numpy"])
def test_check_table_holds_the_decoded_table(tmp_path, backend):
    data, facts = gen.generate(150, 2 ** 31 + 9)
    assert gen.check_table(decoded(data, tmp_path, backend), facts) == []


def spoiled(data: bytes, facts: dict, how: str) -> bytes:
    """The bytes with one thing wrong in the first order of three lines
    or more."""
    raw = bytearray(data)
    offsets = gen.record_offsets(np.frombuffer(data, dtype=np.uint8))
    order = int(np.flatnonzero(facts["counts"] >= 3)[0])
    at = int(offsets[order]) + 4
    count = int(facts["counts"][order])
    if how == "line":
        # the second line's L-QUANTITY, one unit up in its last digit
        raw[at + 59 + 145 + 12 + 6] ^= 0x10
    elif how == "short_list":
        raw[at + 58] = 0xF0 + count - 1
    return bytes(raw)


@pytest.mark.parametrize("how,complaint", [
    ("line", "sum(O_LINES.L_QUANTITY)"),
    ("short_list", "list is not as long as the count drawn"),
])
def test_check_table_catches_a_spoiled_file(tmp_path, how, complaint):
    data, facts = gen.generate(120, 2 ** 31 + 21)
    table = decoded(spoiled(data, facts, how), tmp_path, "numpy")
    wrong = gen.check_table(table, facts)
    assert any(complaint in w for w in wrong), wrong


def test_check_table_catches_a_comment_shifted_by_one_element(tmp_path):
    """What a shift wrong by one element gives: every order's comment
    read 145 B from where it lies. The lists and every sum still hold;
    the comments do not."""
    data, facts = gen.generate(120, 2 ** 31 + 22)
    table = decoded(data, tmp_path, "numpy")
    shifted = []
    for record, count in zip(records_of(data), facts["counts"].tolist()):
        at = 59 + 145 * (count - 1)          # one element short
        text = bytes(record[at:at + 79])
        shifted.append(text.decode("cp037"))
    spoiled_table = table.set_column(
        table.schema.get_field_index("O_COMMENT"), "O_COMMENT",
        pa.array(shifted).cast(table.schema.field("O_COMMENT").type))
    wrong = gen.check_table(spoiled_table, facts)
    assert len(wrong) == 1 and "behind the array" in wrong[0]
    assert wrong[0].startswith("120 orders")
    # and a table a row short is refused before anything else
    assert "rows 119" in gen.check_table(table.slice(1), facts)[0]
