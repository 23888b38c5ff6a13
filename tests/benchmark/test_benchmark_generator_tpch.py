"""The frozen TPC-H lineitem generator: the record's widths and domains,
determinism by seed, facts that merge, a check that catches a spoiled
cell, and its own answers to Q6 and Q1 against pyarrow over the scalar
oracle's decode of its bytes."""
import decimal
import json
import os

import numpy as np
import pytest

import benchmark_testing  # noqa: F401  (puts the repo on sys.path)
from benchmark.generators import tpch_lineitem as gen

CONFIG = os.path.join(benchmark_testing.REPO, "benchmark", "configs",
                      "tpch_lineitem_sf1.json")
OPTIONS = dict(copybook_contents=gen.COPYBOOK,
               schema_retention_policy="collapse_root",
               ebcdic_code_page="cp037")


def decoded(data: bytes, tmp_path, backend: str):
    from cobrix_tpu import read_cobol

    path = tmp_path / f"lineitem.{backend}.dat"
    path.write_bytes(data)
    return read_cobol(str(path), backend=backend, **OPTIONS).to_arrow()


def test_the_record_is_the_configurations():
    from cobrix_tpu import parse_copybook

    with open(CONFIG) as f:
        config = json.load(f)
    copybook = parse_copybook(gen.COPYBOOK)
    assert copybook.record_size == gen.RECORD_SIZE == config["record_bytes"]
    fields = [st for st in copybook.ast.children[0].children]
    assert len(fields) == config["fields"] == 16
    widths = [st.binary_properties.data_size for st in fields]
    assert widths == [4] * 4 + [7] * 4 + [1, 1] + [8] * 3 + [25, 10, 44]
    full = config["full"]
    assert gen.records_for(full["generate_chunk_bytes"]) == 450_395
    chunks = -(-full["file_bytes"] // full["generate_chunk_bytes"])
    assert chunks * 450_395 == config["rows"] == 6_305_530
    assert config["rows"] >= config["source_scale"]["rows"]
    assert config["reduced"] == []
    assert set(config["queries"]) == {"q6", "q1"}


def test_domains_are_the_specifications():
    d = gen.draw(20_000, 2 ** 31 + 5)
    assert d["quantity"].min() == 100 and d["quantity"].max() == 5000
    assert set(np.unique(d["discount"])) == set(range(11))
    assert set(np.unique(d["tax"])) == set(range(9))
    assert d["linenumber"].min() == 1 and d["linenumber"].max() == 7
    assert d["partkey"].min() >= 1 and d["partkey"].max() <= gen.PARTS
    retail = d["price"] * 100 // d["quantity"]
    assert retail.min() >= 90_000 and retail.max() <= 90_000 + 20_000 + 99_900
    assert d["shipdate"].min() >= 19920102
    assert d["shipdate"].max() <= 19981201
    assert (d["receiptdate"] > d["shipdate"]).all()
    assert set(np.unique(d["returnflag"])) == {"A", "N", "R"}
    assert set(np.unique(d["linestatus"])) == {"F", "O"}
    # 'N' exactly where the receipt date is after the current date
    assert ((d["returnflag"] == "N") == (d["receiptdate"] > 19950617)).all()
    assert ((d["linestatus"] == "O") == (d["shipdate"] > 19950617)).all()
    # a line's number counts the lines of its order
    first = d["linenumber"] == 1
    assert (np.diff(d["orderkey"])[~first[1:]] == 0).all()
    # Q6 keeps about 2 % of the rows, Q1 about 98 %
    answers = gen.answers(d)
    assert 0.012 < answers["q6"]["rows"] / 20_000 < 0.028
    q1_rows = sum(g["rows"] for g in answers["q1"].values())
    assert 0.97 < q1_rows / 20_000 < 0.995
    assert sorted(answers["q1"]) == ["AF", "NF", "NO", "RF"]


def test_the_same_seed_gives_the_same_bytes():
    a, facts_a = gen.generate(700, 2 ** 31 + 11)
    b, facts_b = gen.generate(700, 2 ** 31 + 11)
    c, _ = gen.generate(700, 2 ** 31 + 12)
    assert a == b and facts_a == facts_b and a != c
    assert len(a) == 700 * gen.RECORD_SIZE == facts_a["bytes"]


def test_facts_of_chunks_merge_to_the_files(tmp_path):
    parts = [gen.generate(900, seed) for seed in (41, 42, 43)]
    merged = gen.merge_facts([facts for _, facts in parts])
    assert merged["records"] == 2700
    table = decoded(b"".join(data for data, _ in parts), tmp_path, "numpy")
    assert gen.check_table(table, merged) == []
    assert gen.reference_answers(table) == gen.query_answers(merged)


@pytest.mark.parametrize("column,spoiled", [
    ("L_EXTENDEDPRICE", decimal.Decimal("1.00")), ("L_LINENUMBER", 9),
    ("L_SHIPDATE", 19990101), ("L_LINESTATUS", "X")])
def test_check_table_catches_a_spoiled_cell(tmp_path, column, spoiled):
    import pyarrow as pa

    data, facts = gen.generate(400, 77)
    table = decoded(data, tmp_path, "numpy")
    assert gen.check_table(table, facts) == []
    values = table.column(column).to_pylist()
    values[123] = spoiled
    at = table.column_names.index(column)
    bad = table.set_column(at, column, pa.array(
        values, type=table.schema.field(column).type))
    assert len(gen.check_table(bad, facts)) == 1
    assert gen.check_table(table.slice(1), facts)[0].startswith("rows 399")


def test_its_answers_equal_pyarrow_over_the_oracles_decode(tmp_path):
    data, facts = gen.generate(1200, 2 ** 31 + 3)
    oracle = decoded(data, tmp_path, "host")
    assert oracle.equals(decoded(data, tmp_path, "numpy"))
    expected = gen.query_answers(facts)
    assert gen.reference_answers(oracle) == expected
    assert str(gen.reference_answers(oracle)) == str(expected)
    assert [r["count"] for r in expected["q1"]] == [
        facts["answers"]["q1"][k]["rows"] for k in sorted(
            facts["answers"]["q1"])]
    # strings as written: space padded, cp037
    assert set(oracle.column("L_SHIPMODE").to_pylist()) <= set(gen.MODES)
    assert set(oracle.column("L_SHIPINSTRUCT").to_pylist()) \
        <= set(gen.INSTRUCTIONS)


def test_a_sample_is_whole_records_by_seed(tmp_path):
    data, _ = gen.generate(300, 5)
    path, out = tmp_path / "in.dat", tmp_path / "sample.dat"
    path.write_bytes(data)
    idx = gen.sample(str(path), str(out), 25, seed=9)
    rows = np.frombuffer(data, np.uint8).reshape(-1, gen.RECORD_SIZE)
    assert out.read_bytes() == rows[idx].tobytes() and len(idx) == 25
    assert (gen.sample(str(path), str(out), 25, seed=9) == idx).all()
