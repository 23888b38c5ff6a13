"""`query.dataset(...).aggregate(aggs, filter=, group_by=)` on a device
backend against its definition, `_aggregate_by_decode` over the scalar
oracle (`backend="host"`): digit for digit, on seeded TPC-H lineitem bytes
and on a small table built to hold nulls, limit magnitudes and the fields
the device does not take. Nothing here touches a TPU: the programs run
on the CPU, the Pallas kernel through its interpreter."""
import decimal
import os
import time

import numpy as np
import pytest

from benchmark.generators import tpch_lineitem
from benchmark.generators.ebcdic import ENCODE_LUT, encode_comp_be
from cobrix_tpu import api, query
from cobrix_tpu.parallel import query as device_query
from cobrix_tpu.parallel.query import (GROUPS_MAX, KEY_BYTES_MAX,
                                       NotOnDevice)
from cobrix_tpu.stats.aggregate import parse_specs

from util import check_stage_record

pytestmark = pytest.mark.jax

LINEITEM = dict(copybook_contents=tpch_lineitem.COPYBOOK,
                schema_retention_policy="collapse_root")
Q6 = dict(
    aggs=["sum:L_EXTENDEDPRICE*L_DISCOUNT"],
    filter="L_SHIPDATE >= 19940101 and L_SHIPDATE < 19950101 and "
           "L_DISCOUNT >= 0.05 and L_DISCOUNT <= 0.07 and L_QUANTITY < 24")
Q1 = dict(
    group_by=["L_RETURNFLAG", "L_LINESTATUS"], filter="L_SHIPDATE <= 19980902",
    aggs=["sum:L_QUANTITY", "sum:L_EXTENDEDPRICE",
          "sum:L_EXTENDEDPRICE*(1-L_DISCOUNT)",
          "sum:L_EXTENDEDPRICE*(1-L_DISCOUNT)*(1+L_TAX)", "avg:L_QUANTITY",
          "avg:L_EXTENDEDPRICE", "avg:L_DISCOUNT", "count"])
# the offsets of lineitem fields whose bytes the null cases spoil
DISCOUNT_AT, SHIPDATE_AT, RECORD = 30, 46, tpch_lineitem.RECORD_SIZE


def lineitem_bytes(records=3000, seed=5):
    data, facts = tpch_lineitem.generate(records, seed)
    return np.frombuffer(data, dtype=np.uint8).reshape(-1, RECORD).copy(), \
        facts


def same(got, want):
    """Equal, and written alike: the digits, not only the values."""
    if isinstance(want, dict):
        assert got == want
        assert {k: str(v) for k, v in got.items()} \
            == {k: str(v) for k, v in want.items()}
    else:
        assert got.schema == want.schema, (got.schema, want.schema)
        assert got.to_pylist() == want.to_pylist()
        assert str(got.to_pylist()) == str(want.to_pylist())


def ask(path, backend, q, **options):
    dataset = query.dataset(str(path), backend=backend, **options)
    result = dataset.aggregate(q["aggs"], filter=q.get("filter"),
                               group_by=q.get("group_by"))
    return result, dataset.metrics


def device_stats(metrics):
    assert metrics is not None, "the device did not answer"
    return metrics.as_dict()["device"]


@pytest.fixture
def small_chunks(monkeypatch):
    """Read chunks of 1,000 lineitem records: a 3,300-record file is
    three whole chunks and a ragged one."""
    monkeypatch.setattr(api, "FIXED_READ_CHUNK_BYTES", 1000 * RECORD)


LINEITEM_CASES = {
    "q6": Q6,
    "q1": Q1,
    "no_filter": dict(aggs=["count", "sum:L_TAX", "min:L_SHIPDATE",
                            "max:L_EXTENDEDPRICE", "avg:L_LINENUMBER",
                            "sum:L_LINENUMBER*L_QUANTITY"]),
    "nothing_passes": dict(aggs=["count", "sum:L_QUANTITY", "min:L_TAX",
                                 "avg:L_TAX"], filter="L_SHIPDATE < 19000101"),
    "no_group_passes": dict(aggs=["count", "sum:L_QUANTITY"],
                            group_by=["L_LINESTATUS"],
                            filter="L_SHIPDATE < 19000101"),
    "kleene": dict(
        aggs=["count", "sum:L_EXTENDEDPRICE*(1+L_DISCOUNT)"],
        group_by=["L_RETURNFLAG"],
        filter="not (L_DISCOUNT < 0.03 or L_SHIPDATE > 19960101) or "
               "L_TAX in (0.02, 0.05) or L_DISCOUNT == null"),
    "between_units": dict(aggs=["count"],
                          filter="L_DISCOUNT > 0.045 and L_DISCOUNT != 0.055 "
                                 "and L_TAX <= 0.0799 and L_QUANTITY >= 3"),
}


@pytest.mark.parametrize("case,backend", [
    (case, "jax") for case in sorted(LINEITEM_CASES)] + [
    # the Pallas interpreter takes the three widest cases
    (case, "pallas") for case in ("kleene", "q1", "q6")])
def test_lineitem_query_equals_the_scalar_oracle(tmp_path, small_chunks,
                                                 case, backend):
    """3,300 records in four chunks, the last ragged; nulls in an operand
    and filter field (invalid COMP-3 nibbles in L_DISCOUNT) and in a
    filter field (non-digit L_SHIPDATE)."""
    rows, _facts = lineitem_bytes(3300)
    rows[7::13, DISCOUNT_AT + 3] = 0xFA
    rows[5::17, SHIPDATE_AT + 2] = 0x5C
    path = tmp_path / "lineitem.dat"
    path.write_bytes(rows.tobytes())
    q = LINEITEM_CASES[case]
    want, none = ask(path, "host", q, **LINEITEM)
    assert none is None
    same(ask(path, "numpy", q, **LINEITEM)[0], want)
    got, metrics = ask(path, backend, q, **LINEITEM)
    same(got, want)
    device = device_stats(metrics)
    assert device["query_chunks"] == 4
    assert device["query_fallback_chunks"] == 0
    assert device["query_rows_scanned"] == 3300
    assert sorted(device["launches"].values()) == [1, 3]
    if "group_by" in q:
        assert device["query_groups"] == got.num_rows
    if case == "nothing_passes":
        assert got == {"count": 0, "sum:L_QUANTITY": None, "min:L_TAX": None,
                       "avg:L_TAX": None}
    if case == "no_group_passes":
        assert got.num_rows == 0


def test_q6_and_q1_equal_the_generators_own_answers(tmp_path):
    rows, facts = lineitem_bytes(4000, seed=2 ** 31 + 9)
    path = tmp_path / "lineitem.dat"
    path.write_bytes(rows.tobytes())
    expected = tpch_lineitem.query_answers(facts)
    q6, metrics = ask(path, "jax", Q6, **LINEITEM)
    assert q6 == expected["q6"]
    assert device_stats(metrics)["d2h_bytes"] < 1000
    q1, metrics = ask(path, "jax", Q1, **LINEITEM)
    assert q1.to_pylist() == expected["q1"]
    assert q1.column_names[:2] == ["L_RETURNFLAG", "L_LINESTATUS"]
    device = device_stats(metrics)
    assert device["query_rows_passed"] == sum(r["count"]
                                              for r in expected["q1"])
    # the projection sends the query's bytes and no others
    assert device["launches"] == {"4096x38": 1}
    assert device["h2d_bytes"] == 4096 * 38 and device["d2h_bytes"] < 4096


def test_a_literal_meets_a_decimal_field_exactly(tmp_path):
    """0.05 is five hundredths on every path, not the float beside it."""
    rows, facts = lineitem_bytes(2000)
    path = tmp_path / "lineitem.dat"
    path.write_bytes(rows.tobytes())
    drawn = tpch_lineitem.draw(2000, 5)["discount"]
    q = dict(aggs=["count", "min:L_DISCOUNT"], filter="L_DISCOUNT >= 0.05")
    for backend in ("host", "numpy", "jax"):
        got, _ = ask(path, backend, q, **LINEITEM)
        assert got == {"count": int((drawn >= 5).sum()),
                       "min:L_DISCOUNT": decimal.Decimal("0.05")}, backend


def test_a_group_present_in_one_chunk_only(tmp_path, small_chunks):
    rows, _ = lineitem_bytes(2500)
    rows[2100, 44] = ENCODE_LUT[ord("Z")]       # L_RETURNFLAG of one row
    path = tmp_path / "lineitem.dat"
    path.write_bytes(rows.tobytes())
    q = dict(aggs=["count", "sum:L_QUANTITY"], group_by=["L_RETURNFLAG"])
    got, metrics = ask(path, "jax", q, **LINEITEM)
    same(got, ask(path, "host", q, **LINEITEM)[0])
    assert got.column("L_RETURNFLAG").to_pylist()[-1] == "Z"
    assert got.column("count").to_pylist()[-1] == 1
    assert device_stats(metrics)["query_chunks"] == 3


def test_sums_past_2_53_are_exact(tmp_path):
    """Prices of twelve digits: sum_charge leaves 2^53 within a few
    rows, so a float64 accumulator cannot hold its digits; int64 with
    limbs does, with no fallback (a row's product is under 2^63)."""
    rows, _ = lineitem_bytes(1500)
    rng = np.random.default_rng(3)
    price = rng.integers(10 ** 11, 10 ** 12, size=len(rows))
    rows[:, 23:30] = tpch_lineitem._comp3(price)
    path = tmp_path / "lineitem.dat"
    path.write_bytes(rows.tobytes())
    got, metrics = ask(path, "jax", Q1, **LINEITEM)
    same(got, ask(path, "host", Q1, **LINEITEM)[0])
    assert device_stats(metrics)["query_fallback_chunks"] == 0
    charges = got.column(Q1["aggs"][3]).to_pylist()
    exact = [int(c.scaleb(6)) for c in charges]
    assert all(e > 2 ** 53 for e in exact)
    assert any(int(float(e)) != e for e in exact)


# -- a small table with what lineitem lacks ---------------------------------

MINI = """
       01  R.
           05  K        PIC 9(2).
           05  F        PIC X.
           05  A        PIC S9(10)V99 COMP-3.
           05  B        PIC S9(10)V99 COMP-3.
           05  C        PIC S9(10)V99 COMP-3.
           05  D        PIC S9(4) COMP.
           05  W        PIC S9(20) COMP-3.
           05  FL       COMP-1.
           05  ARR      OCCURS 3 TIMES.
               10  E    PIC S9(4) COMP.
           05  T        PIC X(3).
"""
MINI_OPTIONS = dict(copybook_contents=MINI,
                    schema_retention_policy="collapse_root")


def mini_bytes(n, seed, keys=7, limit=False):
    """`n` records of MINI: keys of `keys` values (some not digits, so
    null), flags, three decimals (at the PIC's limit if asked), a COMP,
    a wide decimal, a float, an array and a string."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, keys, size=n)
    top = 10 ** 12 - 1
    draw = (lambda: np.full(n, top)) if limit else \
        (lambda: rng.integers(0, 10 ** 7, size=n))
    parts = [
        np.stack([0xF0 + k // 10, 0xF0 + k % 10], axis=1).astype(np.uint8),
        ENCODE_LUT[rng.choice([ord("x"), ord("y"), ord(" ")], size=n)
                   ][:, None],
        tpch_lineitem._comp3(draw()), tpch_lineitem._comp3(draw()),
        tpch_lineitem._comp3(draw()),
        encode_comp_be(rng.integers(-900, 900, size=n), 2),
        tpch_lineitem._comp3(rng.integers(0, 10 ** 15, size=n), 11),
        rng.random(n).astype(">f4").view(np.uint8).reshape(n, 4),
        encode_comp_be(rng.integers(0, 99, size=3 * n), 2).reshape(n, 6),
        ENCODE_LUT[rng.choice([ord("a"), ord("b")], size=(n, 3))],
    ]
    rows = np.concatenate(parts, axis=1)
    rows[3::11, 0] = 0x5C       # a key that is no number: null
    rows[4::7, 4] = 0xBB        # A, invalid nibbles: null
    return rows


MINI_DEVICE_CASES = {
    "null_key": dict(aggs=["count", "sum:A", "avg:B", "min:D", "max:D",
                           "sum:A*D"], group_by=["K"]),
    "two_keys": dict(aggs=["count", "sum:A*(1-B)"], group_by=["F"],
                     filter="D > -100 and A != null"),
    "is_null": dict(aggs=["count", "sum:B"], filter="A == null or K == null"),
}


@pytest.mark.parametrize("case", sorted(MINI_DEVICE_CASES))
def test_nulls_in_keys_operands_and_filters(tmp_path, case):
    path = tmp_path / "mini.dat"
    path.write_bytes(mini_bytes(1200, seed=1).tobytes())
    q = MINI_DEVICE_CASES[case]
    want, _ = ask(path, "host", q, **MINI_OPTIONS)
    got, metrics = ask(path, "jax", q, **MINI_OPTIONS)
    same(got, want)
    assert device_stats(metrics)["query_fallback_chunks"] == 0
    if case == "null_key":
        assert got.column("K").to_pylist()[-1] is None     # nulls last
        assert got.num_rows == 8


@pytest.mark.parametrize("case", ["products_past_63_bits", "too_many_keys"])
def test_a_chunk_the_device_cannot_prove_goes_to_the_host_counted(
        tmp_path, case):
    path = tmp_path / "mini.dat"
    if case == "products_past_63_bits":
        # three factors at the PIC's limit: 10^36 a row
        path.write_bytes(mini_bytes(600, seed=2, limit=True).tobytes())
        q = dict(aggs=["sum:A*B*C", "sum:A*(1-B)*(1+C)", "count"],
                 group_by=["F"])
    else:
        path.write_bytes(mini_bytes(900, seed=2,
                                    keys=GROUPS_MAX + 5).tobytes())
        q = dict(aggs=["count", "sum:A"], group_by=["K"])
    want, _ = ask(path, "host", q, **MINI_OPTIONS)
    got, metrics = ask(path, "jax", q, **MINI_OPTIONS)
    same(got, want)
    device = device_stats(metrics)
    assert device["query_chunks"] == device["query_fallback_chunks"] == 1
    assert device["stage_n"]["query.fallback"] == 1
    if case == "products_past_63_bits":
        assert max(got.column("sum:A*B*C").to_pylist()) > 10 ** 30


@pytest.mark.parametrize("q", [
    dict(aggs=["sum:FL"]),                                # a float operand
    dict(aggs=["sum:W"]),                                 # past 18 digits
    dict(aggs=["count"], filter="T == 'aba'"),            # a string predicate
    dict(aggs=["count"], group_by=["T"]),                 # a 3-byte key
    dict(aggs=["count"], group_by=["K", "F"]),            # 3 bytes of keys
    dict(aggs=["count"], filter="FL > 0.5"),              # a float predicate
], ids=["float", "wide", "string_filter", "wide_key", "two_keys",
        "float_filter"])
def test_what_the_device_does_not_take_is_decoded(tmp_path, q):
    assert KEY_BYTES_MAX == 2
    path = tmp_path / "mini.dat"
    path.write_bytes(mini_bytes(500, seed=4).tobytes())
    got, metrics = ask(path, "jax", q, **MINI_OPTIONS)
    assert metrics is None, "the host route was not taken"
    want, _ = ask(path, "numpy", q, **MINI_OPTIONS)
    same(got, want)


def test_occurs_operand_is_refused_alike_on_every_backend(tmp_path):
    path = tmp_path / "mini.dat"
    path.write_bytes(mini_bytes(50, seed=4).tobytes())
    for backend in ("numpy", "jax"):
        with pytest.raises(KeyError, match="not a primitive column"):
            ask(path, backend, dict(aggs=["sum:E"]), **MINI_OPTIONS)


@pytest.mark.parametrize("options", [
    dict(record_error_policy="permissive"), dict(record_start_offset="1"),
    dict(pipeline_workers="2")], ids=["permissive", "offset", "pipeline"])
def test_a_read_that_is_not_plain_fixed_length_is_decoded(tmp_path, options):
    rows = mini_bytes(300, seed=6)
    if "record_start_offset" in options:
        rows = np.concatenate([np.zeros((300, 1), np.uint8), rows], axis=1)
    path = tmp_path / "mini.dat"
    path.write_bytes(rows.tobytes())
    q = dict(aggs=["count", "sum:B"], group_by=["F"])
    got, metrics = ask(path, "jax", q, **MINI_OPTIONS, **options)
    assert metrics is None
    same(got, ask(path, "numpy", q, **MINI_OPTIONS, **options)[0])


# -- grammar, routing, counters ---------------------------------------------

@pytest.mark.parametrize("spelling,text", [
    ("count", "count"), (" SUM : A ", "sum:A"), ("avg:A-B", "avg:A-B"),
    ("sum: A * ( 1 - B ) * (1+C)", "sum:A*(1-B)*(1+C)"),
    ("sum:(1-A)", "sum:(1-A)"), ("Min:X.Y", "min:X.Y"), ("max:A", "max:A")])
def test_spec_grammar_accepts(spelling, text):
    (spec,) = parse_specs([spelling])
    assert spec.text == text
    fn, field = spec          # what a spec was before it could be a product
    assert fn == text.split(":")[0]
    assert field == (None if "*" in text or "(" in text or fn == "count"
                     else text.split(":")[1])


@pytest.mark.parametrize("spelling", [
    "", "count:A", "sum", "sum:", "avg:A*B", "min:(1-A)", "max:A*B",
    "sum:A+B", "sum:(2-A)", "sum:(1*A)", "sum:A**B", "sum:A*", "median:A",
    "sum:(1-A", "sum:1-A", "sum:A*(B)"])
def test_spec_grammar_refuses(spelling):
    with pytest.raises(ValueError, match="unsupported"):
        parse_specs([spelling])


def test_no_spec_at_all_is_refused():
    with pytest.raises(ValueError, match="at least one"):
        parse_specs([])


@pytest.mark.parametrize("q", [
    dict(aggs=["sum:AMOUNT*AMOUNT"]), dict(aggs=["avg:AMOUNT"]),
    dict(aggs=["sum:AMOUNT"], group_by=["CURRENCY"])],
    ids=["product", "avg", "group_by"])
def test_the_stats_short_cut_declines_what_it_cannot_prove(tmp_path, q):
    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing.generators import generate_transactions
    from test_stats import FIXED_OPTS

    path = tmp_path / "trans.dat"
    path.write_bytes(bytes(generate_transactions(400, seed=3)))
    cache = str(tmp_path / "cache")
    read_cobol(str(path), cache_dir=cache, collect_stats="true",
               stats_chunk_mb="0.01", **FIXED_OPTS)
    warm = query.dataset(str(path), cache_dir=cache, use_stats="true",
                         **FIXED_OPTS)
    specs = parse_specs(q["aggs"])
    assert warm._aggregate_from_stats(parse_specs(["sum:AMOUNT"])) is not None
    if "group_by" not in q:
        assert warm._aggregate_from_stats(specs) is None
    plain = query.dataset(str(path), **FIXED_OPTS)
    same(warm.aggregate(q["aggs"], group_by=q.get("group_by")),
         plain.aggregate(q["aggs"], group_by=q.get("group_by")))


def test_bind_refuses_before_a_byte_is_read(tmp_path):
    path = tmp_path / "absent.dat"
    path.write_bytes(mini_bytes(10, seed=1).tobytes())
    dataset = query.dataset(str(path), backend="jax", **MINI_OPTIONS)
    os.unlink(path)
    with pytest.raises(NotOnDevice):
        api.aggregate_on_device(
            dataset.files, MINI, dataset.options, "jax",
            parse_specs(["sum:FL"]), None, [], dataset.schema)


def test_stages_and_counters_of_a_device_aggregate(tmp_path, small_chunks):
    rows, _ = lineitem_bytes(2400)
    path = tmp_path / "lineitem.dat"
    path.write_bytes(rows.tobytes())
    ask(path, "jax", Q1, **LINEITEM)          # the program is built
    t0 = time.perf_counter()
    got, metrics = ask(path, "jax", Q1, **LINEITEM)
    wall = time.perf_counter() - t0
    out = metrics.as_dict()
    device = out["device"]
    check_stage_record(device, wall, {
        "query.bind", "scan", "read", "frame", "pack", "h2d", "launch",
        "d2h_wait", "query.merge"})
    assert "query.fallback" not in device["stage_s"]
    assert "compile" not in device["stage_s"], "the program was not found"
    assert device["compiles"] == 0
    assert device["stage_n"]["pack"] == device["stage_n"]["read"] == 3
    assert device["stage_n"]["query.bind"] == 1
    assert {k: v for k, v in device.items() if k.startswith("query_")} == {
        "query_chunks": 3, "query_fallback_chunks": 0,
        "query_rows_scanned": 2400,
        "query_rows_passed": sum(got.column("count").to_pylist()),
        "query_groups": got.num_rows}
    assert device["records"] == 2400 and out["records"] == 2400
    assert out["bytes_read"] == 2400 * RECORD
    assert device["device_groups"] == {"fused": 0, "fused_rows_in_lanes": 0,
                                       "sliced": 3, "gathered": 0}
    # a read's record has no query counts
    from cobrix_tpu import read_cobol
    read = read_cobol(str(path), backend="jax", **LINEITEM)
    assert not any(k.startswith("query_")
                   for k in read.metrics.as_dict()["device"])


def test_device_scopes_are_in_the_program(tmp_path):
    import jax

    path = tmp_path / "lineitem.dat"
    path.write_bytes(lineitem_bytes(300)[0].tobytes())
    ask(path, "jax", Q6, **LINEITEM)
    (held,) = [a for cb, a in device_query._AGGREGATORS.values()
               if a.query.specs[0].text == Q6["aggs"][0]
               and a.decoder.backend == "jax"][-1:]
    text = held.device_program()._jit.lower(
        jax.ShapeDtypeStruct((512, held.decoder.plan.max_extent), np.uint8),
        jax.ShapeDtypeStruct((), np.int32)).as_text(debug_info=True)
    assert "cobrix.filter" in text and "cobrix.reduce" in text
    # no scan, sort or scatter: ROADMAP C11 asks a parity check of any
    for op in ("stablehlo.sort", "stablehlo.scatter", "reduce_window",
               "stablehlo.while"):
        assert op not in text, op
