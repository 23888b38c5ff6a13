"""Query-pushdown parity matrix + expression semantics (ISSUE 13).

The invariant every test here pins: a `select`/`filter` pushed-down
read is BYTE-IDENTICAL to the full decode post-hoc projected/filtered
with pyarrow — across fixed/VRL/hierarchical layouts, sequential/
pipelined/multihost execution, the serve streamed surface (incl.
resume-token failover mid-filtered-stream), and the pyarrow-dataset
scan adapter. Plus: the pruning counters tell the truth, plan caches
never cross-contaminate between different projections, and resume
fingerprints change when the filter changes.
"""
import json
import os
import tempfile

import numpy as np
import pytest

import pyarrow as pa
import pyarrow.compute as pc

from cobrix_tpu import read_cobol
from cobrix_tpu.query import (
    And,
    IsIn,
    col,
    dataset,
    parse_filter,
    segment_is,
)
from cobrix_tpu.query.expr import from_wire, normalize_filter
from cobrix_tpu.testing.generators import (
    EXP3_COPYBOOK,
    HIERARCHICAL_COPYBOOK,
    HIERARCHICAL_PARENT_MAP,
    HIERARCHICAL_SEGMENT_MAP,
    TRANSDATA_COPYBOOK,
    generate_exp3,
    generate_hierarchical,
    generate_transactions,
)

from util import hard_timeout


def _posthoc(table, mask):
    return table.filter(pc.fill_null(mask, False))


@pytest.fixture(scope="module")
def fixed_file():
    path = tempfile.mktemp(suffix=".dat")
    with open(path, "wb") as f:
        f.write(bytes(generate_transactions(600, seed=11)))
    yield path
    os.unlink(path)


@pytest.fixture(scope="module")
def vrl_file():
    path = tempfile.mktemp(suffix=".dat")
    with open(path, "wb") as f:
        f.write(bytes(generate_exp3(250, seed=11)))
    yield path
    os.unlink(path)


FIXED_OPTS = dict(copybook_contents=TRANSDATA_COPYBOOK,
                  schema_retention_policy="collapse_root")
VRL_OPTS = dict(copybook_contents=EXP3_COPYBOOK,
                is_record_sequence="true", segment_field="SEGMENT_ID",
                schema_retention_policy="collapse_root",
                redefine_segment_id_map="STATIC-DETAILS => C",
                **{"redefine-segment-id-map:1": "CONTACTS => P"})


# -- expression AST / grammar / wire form --------------------------------

class TestExpressions:
    def test_grammar_str_roundtrip(self):
        e = parse_filter(
            "CURRENCY in ('USD', 'EUR') and (AMOUNT > 100 or "
            "not (WEALTH_QFY == 1))")
        again = parse_filter(str(e))
        assert e.canonical() == again.canonical()

    def test_wire_roundtrip(self):
        e = (col("A") == "x") & ~(col("B") <= 3) | col("C").isin([1, 2])
        wire = e.canonical()
        assert from_wire(wire).canonical() == wire
        assert json.loads(wire)["op"] == "or"

    def test_builder_equals_grammar(self):
        b = (col("CURRENCY") == "USD") & (col("AMOUNT") > 100)
        g = parse_filter("CURRENCY == 'USD' and AMOUNT > 100")
        assert b.canonical() == g.canonical()

    def test_segment_builder(self):
        e = segment_is("C", "P")
        assert parse_filter(str(e)).canonical() == e.canonical()

    def test_pyarrow_expression_reprs_parse(self):
        e = parse_filter(str((pc.field("A") == "x") & (pc.field("B") > 5)))
        assert sorted(e.fields()) == ["A", "B"]
        e2 = parse_filter(str(pc.field("CUR").isin(["USD", "EUR"])))
        assert isinstance(e2, IsIn)
        assert e2.values == ("USD", "EUR")
        e3 = parse_filter(str(~(pc.field("N") < 3)))
        assert "not" in str(e3)

    def test_keyword_combination_raises(self):
        with pytest.raises(TypeError, match="bitwise"):
            bool(col("A") == 1)

    def test_normalize_is_deterministic(self):
        w1 = normalize_filter("B > 5 and A == 'x'")
        assert w1 == normalize_filter(from_wire(w1))
        assert normalize_filter(None) is None
        assert normalize_filter("") is None

    def test_parse_errors(self):
        for bad in ("AMOUNT >", "and A == 1", "A ==", "A in ()",
                    "A == 'x' garbage"):
            with pytest.raises(ValueError):
                parse_filter(bad)

    def test_field_to_field_comparison_not_mislowered(self):
        """The repr of pc.field('A') == pc.field('B') must NOT parse
        with the RHS silently read as the string literal 'B' — the
        dataset scanner needs the parse failure to take its documented
        post-hoc fallback."""
        from cobrix_tpu.query.dataset import _lower_filter

        with pytest.raises(ValueError, match="bare name"):
            parse_filter("NAME == ALIAS")
        wire, posthoc = _lower_filter(
            pc.field("NAME") == pc.field("ALIAS"))
        assert wire is None and posthoc is not None

    def test_keyword_named_field_survives_serialization(self):
        """A field legally named like a grammar keyword (SEGMENT, IN,
        NOT...) round-trips through the builder -> option layer -> wire
        (str()'s grammar spelling cannot express it; canonical() can)."""
        from cobrix_tpu.api import Options, _normalize_filter_option

        e = col("SEGMENT") == "C"
        opts = Options({"filter": e})
        wire = _normalize_filter_option(opts.get("filter"))
        assert from_wire(wire).canonical() == e.canonical()

    def test_incomplete_wire_json_is_a_value_error(self):
        # a buggy serve client's wire dict must surface as the option
        # error it is, never a bare KeyError
        for bad in ('{"op": "=="}', '{"op": "in", "field": "A"}',
                    '{"op": "and"}', '{"op": "not"}'):
            with pytest.raises(ValueError, match="missing key"):
                from_wire(bad)

    def test_bad_fields_rejected_at_read(self, fixed_file):
        with pytest.raises(ValueError, match="not found"):
            read_cobol(fixed_file, filter="NO_SUCH_FIELD == 1",
                       **FIXED_OPTS)
        with pytest.raises(ValueError, match="segment_field"):
            read_cobol(fixed_file, filter="segment('C')", **FIXED_OPTS)

    def test_array_field_rejected(self, vrl_file):
        with pytest.raises(ValueError, match="OCCURS"):
            read_cobol(vrl_file, filter="NUM1 > 0", **VRL_OPTS)

    def test_nested_segment_rejected(self, vrl_file):
        with pytest.raises(ValueError, match="conjunct"):
            read_cobol(vrl_file,
                       filter="segment('C') or COMPANY_ID == 'x'",
                       **VRL_OPTS)

    def test_host_backend_rejected(self, fixed_file):
        with pytest.raises(ValueError, match="host"):
            read_cobol(fixed_file, backend="host",
                       filter="CURRENCY == 'USD'", **FIXED_OPTS)


# -- parity matrix --------------------------------------------------------

FIXED_FILTER = "CURRENCY in ('USD', 'EUR') and AMOUNT > 0"


def _fixed_mask(t):
    import decimal

    return pc.and_kleene(
        pc.is_in(t["CURRENCY"], value_set=pa.array(["USD", "EUR"])),
        pc.greater(t["AMOUNT"], pa.scalar(decimal.Decimal(0))))


EXECUTION_GRID = [
    {},
    {"pipeline_workers": "2", "chunk_size_mb": "0.02"},
    {"hosts": "2"},
]


class TestFixedParity:
    @pytest.mark.parametrize("extra", EXECUTION_GRID,
                             ids=["sequential", "pipelined", "multihost"])
    def test_filter_matches_posthoc(self, fixed_file, extra):
        with hard_timeout(300, "fixed parity"):
            full = read_cobol(fixed_file, **FIXED_OPTS,
                              **extra).to_arrow()
            got = read_cobol(fixed_file, filter=FIXED_FILTER,
                             **FIXED_OPTS, **extra).to_arrow()
            assert got.equals(_posthoc(full, _fixed_mask(full)))

    def test_select_filter_late_materialization(self, fixed_file):
        full = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
        got = read_cobol(fixed_file, select="COMPANY_NAME",
                         filter=FIXED_FILTER, **FIXED_OPTS).to_arrow()
        expect = _posthoc(full, _fixed_mask(full))
        assert got.num_rows == expect.num_rows
        assert got["COMPANY_NAME"].equals(expect["COMPANY_NAME"])
        # the filter columns decoded for the predicate but were NOT
        # assembled (legacy select semantics: unselected -> null)
        assert got["CURRENCY"].null_count == got.num_rows
        assert got["AMOUNT"].null_count == got.num_rows

    def test_rows_and_json_agree_with_arrow(self, fixed_file):
        data = read_cobol(fixed_file, filter=FIXED_FILTER, **FIXED_OPTS)
        table = data.to_arrow()
        rows = data.to_dicts()
        assert len(rows) == table.num_rows == len(data)
        assert [r["CURRENCY"] for r in rows] == \
            table["CURRENCY"].to_pylist()

    def test_counters_report_pruning(self, fixed_file):
        data = read_cobol(fixed_file, filter="CURRENCY == 'USD'",
                          **FIXED_OPTS)
        pd = data.metrics.pushdown
        assert pd["records_scanned"] == 600
        assert pd["records_pruned"] == 600 - len(data)
        assert pd["records_pruned_filter"] == pd["records_pruned"]
        assert pd["bytes_skipped"] == pd["records_pruned"] * 45
        assert 0 < pd["selectivity"] < 1

    def test_prometheus_counters_accumulate(self, fixed_file):
        from cobrix_tpu.obs.metrics import scan_metrics

        m = scan_metrics()
        before = m["records_pruned"].value(depth="filter")
        data = read_cobol(fixed_file, filter="CURRENCY == 'USD'",
                          **FIXED_OPTS)
        after = m["records_pruned"].value(depth="filter")
        assert after - before == data.metrics.pushdown[
            "records_pruned_filter"]


class TestVrlParity:
    @pytest.mark.parametrize("extra", EXECUTION_GRID,
                             ids=["sequential", "pipelined", "multihost"])
    def test_segment_and_value_filter(self, vrl_file, extra):
        with hard_timeout(300, "vrl parity"):
            full = read_cobol(vrl_file, **VRL_OPTS, **extra).to_arrow()
            got = read_cobol(
                vrl_file,
                filter=segment_is("C") & (col("COMPANY_ID") != ""),
                **VRL_OPTS, **extra).to_arrow()
            mask = pc.and_kleene(pc.equal(full["SEGMENT_ID"], "C"),
                                 pc.not_equal(full["COMPANY_ID"], ""))
            assert got.equals(_posthoc(full, mask))

    @pytest.mark.parametrize("extra", EXECUTION_GRID[:2],
                             ids=["sequential", "pipelined"])
    def test_select_and_in_filter_leave_the_occurs_plane_out(
            self, vrl_file, extra):
        """`select` of three fields beside an `in` filter on the wide
        copybook: the rows and the selected columns are the full
        decode's filtered afterwards, and the 2000-slot OCCURS that
        neither names comes back without a value."""
        full = read_cobol(vrl_file, **VRL_OPTS, **extra).to_arrow()
        ids = sorted(set(full["COMPANY_ID"].to_pylist()))[:3]
        got = read_cobol(
            vrl_file, select="SEGMENT-ID,COMPANY-ID,COMPANY-NAME",
            filter="COMPANY_ID in (%s)" % ", ".join(
                f"'{i}'" for i in ids),
            **VRL_OPTS, **extra).to_arrow()
        expect = _posthoc(full, pc.is_in(full["COMPANY_ID"],
                                         value_set=pa.array(ids)))
        assert 0 < got.num_rows == expect.num_rows < full.num_rows
        for name in ("SEGMENT_ID", "COMPANY_ID"):
            assert got[name].equals(expect[name])

        def details(table, *path):
            return pc.struct_field(
                table["STATIC_DETAILS"].combine_chunks(), list(path))

        def num1_slots(table):
            return pc.struct_field(pc.list_flatten(
                details(table, "STRATEGY", "STRATEGY_DETAIL")), "NUM1")

        assert details(got, "COMPANY_NAME").equals(
            details(expect, "COMPANY_NAME"))
        assert 0 < len(num1_slots(got)) == num1_slots(got).null_count
        assert num1_slots(expect).null_count == 0

    def test_segment_conjunct_drops_pre_decode(self, vrl_file):
        data = read_cobol(vrl_file, filter=segment_is("P"), **VRL_OPTS)
        pd = data.metrics.pushdown
        assert pd["records_pruned_segment"] > 0
        assert pd["records_pruned_filter"] == 0
        assert pd["bytes_skipped"] > 0
        full = read_cobol(vrl_file, **VRL_OPTS).to_arrow()
        assert len(data) == _posthoc(
            full, pc.equal(full["SEGMENT_ID"], "P")).num_rows

    def test_segment_owned_field_null_on_other_segments(self, vrl_file):
        """A predicate on a field inside one redefine keeps only that
        segment's matching rows — other segments' records compare null
        and drop, byte-identical to post-hoc nested filtering."""
        full = read_cobol(vrl_file, **VRL_OPTS).to_arrow()
        got = read_cobol(vrl_file, filter="TAXPAYER_TYPE == 'A'",
                         **VRL_OPTS).to_arrow()
        tp = pc.struct_field(
            pc.struct_field(full["STATIC_DETAILS"], "TAXPAYER"),
            "TAXPAYER_TYPE")
        assert got.equals(_posthoc(full, pc.equal(tp, "A")))

    def test_record_ids_survive_filtering(self, vrl_file):
        opts = dict(VRL_OPTS, generate_record_id="true")
        full = read_cobol(vrl_file, **opts).to_arrow()
        got = read_cobol(vrl_file, filter=segment_is("C"),
                         **opts).to_arrow()
        expect = _posthoc(full, pc.equal(full["SEGMENT_ID"], "C"))
        assert got["Record_Id"].equals(expect["Record_Id"])


class TestHierarchicalParity:
    @pytest.fixture(scope="class")
    def hier_file(self):
        path = tempfile.mktemp(suffix=".dat")
        with open(path, "wb") as f:
            f.write(bytes(generate_hierarchical(40, seed=13)))
        yield path
        os.unlink(path)

    HOPTS = dict(
        copybook_contents=HIERARCHICAL_COPYBOOK,
        is_record_sequence="true", segment_field="SEGMENT-ID",
        **{f"redefine_segment_id_map:{i}": f"{name} => {sid}"
           for i, (sid, name) in enumerate(
               HIERARCHICAL_SEGMENT_MAP.items())},
        **{f"segment-children:{i}": f"{parent} => {child}"
           for i, (child, parent) in enumerate(
               HIERARCHICAL_PARENT_MAP.items())})

    def test_residual_filter_matches_posthoc(self, hier_file):
        full = read_cobol(hier_file, **self.HOPTS).to_arrow()
        tax = pc.struct_field(
            pc.struct_field(full["ENTITY"], "COMPANY"), "TAXPAYER")
        med = int(np.median([v for v in tax.to_pylist()
                             if v is not None]))
        got_data = read_cobol(hier_file, filter=f"TAXPAYER > {med}",
                              **self.HOPTS)
        got = got_data.to_arrow()
        assert got.equals(_posthoc(full, pc.greater(tax, med)))
        pd = got_data.metrics.pushdown
        assert pd["records_pruned_residual"] == pd["records_pruned"] > 0
        assert len(got_data.to_rows()) == got.num_rows


# -- plan-cache / fingerprint regressions --------------------------------

class TestPlanIsolation:
    def test_plan_cache_no_cross_contamination(self, fixed_file):
        """Same copybook, different select/filter: each read's output
        must reflect ITS projection — a cache hit on the wrong pruned
        plan would null the wrong columns (regression for the
        (copybook, segment, select)-keyed plan LRU)."""
        a = read_cobol(fixed_file, select="CURRENCY",
                       **FIXED_OPTS).to_arrow()
        b = read_cobol(fixed_file, select="COMPANY_ID",
                       **FIXED_OPTS).to_arrow()
        c = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
        assert a["CURRENCY"].null_count == 0
        assert a["COMPANY_ID"].null_count == a.num_rows
        assert b["COMPANY_ID"].null_count == 0
        assert b["CURRENCY"].null_count == b.num_rows
        assert c["CURRENCY"].equals(a["CURRENCY"])
        assert c["COMPANY_ID"].equals(b["COMPANY_ID"])
        # and filters: different predicates, same copybook object
        fa = read_cobol(fixed_file, filter="CURRENCY == 'USD'",
                        **FIXED_OPTS)
        fb = read_cobol(fixed_file, filter="CURRENCY == 'EUR'",
                        **FIXED_OPTS)
        usd = {r["CURRENCY"] for r in fa.to_dicts()}
        eur = {r["CURRENCY"] for r in fb.to_dicts()}
        assert usd <= {"USD"} and eur <= {"EUR"}

    def test_plan_fingerprint_depends_on_filter(self, fixed_file):
        """Two requests differing only in select/filter must carry
        DIFFERENT chunk-plan fingerprints: resuming a filtered stream
        against a differently-filtered plan would splice row sets."""
        from cobrix_tpu.serve.session import plan_fingerprint

        base = {"copybook_contents": TRANSDATA_COPYBOOK}
        fp0 = plan_fingerprint([fixed_file], dict(base))
        fp1 = plan_fingerprint([fixed_file],
                               dict(base, filter="CURRENCY == 'USD'"))
        fp2 = plan_fingerprint([fixed_file],
                               dict(base, filter="CURRENCY == 'EUR'"))
        fp3 = plan_fingerprint([fixed_file],
                               dict(base, select="CURRENCY"))
        assert len({fp0, fp1, fp2, fp3}) == 4
        # same filter, same fingerprint (replica failover depends on it)
        assert fp1 == plan_fingerprint(
            [fixed_file], dict(base, filter="CURRENCY == 'USD'"))


# -- serve: streamed + follow + failover ---------------------------------

class TestServeSurface:
    def test_streamed_filtered_scan_matches_local(self, fixed_file):
        from cobrix_tpu.serve import ScanServer, stream_scan

        srv = ScanServer().start()
        try:
            with hard_timeout(180, "serve filtered stream"):
                local = read_cobol(fixed_file, filter=FIXED_FILTER,
                                   **FIXED_OPTS).to_arrow()
                with stream_scan(srv.address, fixed_file,
                                 filter=FIXED_FILTER,
                                 **FIXED_OPTS) as s:
                    streamed = pa.Table.from_batches(list(s))
                    summary = s.summary
                assert streamed.replace_schema_metadata(None).equals(
                    local.replace_schema_metadata(None))
                pd = summary["metrics"]["pushdown"]
                assert pd["records_pruned"] == 600 - local.num_rows
        finally:
            srv.stop()

    def test_failover_mid_filtered_stream(self, fixed_file):
        """Replica dies mid-filtered-stream; the resumed attempt on
        replica 2 must continue the FILTERED row sequence (the resume
        token's plan fingerprint includes the filter) and assemble a
        table identical to an uninterrupted filtered read."""
        from cobrix_tpu.serve import ScanServer, fetch_table
        from test_resume import _CuttingProxy

        opts = dict(FIXED_OPTS, filter="CURRENCY in ('USD', 'EUR')",
                    chunk_size_mb="0.02", pipeline_workers="2")
        srv = ScanServer().start()
        try:
            with hard_timeout(240, "filtered cut+resume"):
                local = read_cobol(fixed_file, **opts).to_arrow()
                proxy = _CuttingProxy(srv.address, cut_after=8 * 1024)
                try:
                    t = fetch_table([proxy.address, srv.address],
                                    fixed_file, replica_seed=0,
                                    **opts)
                finally:
                    proxy.stop()
                assert t.equals(local)
        finally:
            srv.stop()


# -- dataset scan surface -------------------------------------------------

class TestDatasetSurface:
    def test_scanner_matches_posthoc(self, fixed_file):
        dset = dataset(fixed_file, **FIXED_OPTS)
        full = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
        expr = (pc.field("CURRENCY") == "USD")
        got = dset.scanner(columns=["COMPANY_ID", "AMOUNT"],
                           filter=expr).to_table()
        expect = _posthoc(full, pc.equal(full["CURRENCY"], "USD")
                          ).select(["COMPANY_ID", "AMOUNT"])
        assert got.equals(expect)
        assert dset.count_rows(filter=expr) == expect.num_rows

    def test_reader_and_fragments(self, fixed_file):
        dset = dataset(fixed_file, **FIXED_OPTS)
        frags = dset.get_fragments()
        assert len(frags) == 1
        expr = pc.field("CURRENCY").isin(["USD", "EUR"])
        via_frag = frags[0].scanner(columns=["CURRENCY"],
                                    filter=expr).to_table()
        via_reader = dset.scanner(columns=["CURRENCY"],
                                  filter=expr).to_reader().read_all()
        assert via_frag.equals(via_reader)
        assert set(via_frag["CURRENCY"].to_pylist()) <= {"USD", "EUR"}

    def test_unsupported_pyarrow_expr_falls_back_posthoc(self,
                                                         fixed_file):
        dset = dataset(fixed_file, **FIXED_OPTS)
        # a compute-function expression the grammar cannot lower
        expr = pc.field("COMPANY_ID").is_valid()
        got = dset.scanner(filter=expr).to_table()
        full = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
        import pyarrow.dataset as pads

        assert got.num_rows == pads.dataset(full).to_table(
            filter=expr).num_rows

    def test_unknown_column_rejected(self, fixed_file):
        dset = dataset(fixed_file, **FIXED_OPTS)
        with pytest.raises(KeyError):
            dset.scanner(columns=["NOPE"])

    def test_generated_column_filter_falls_back_posthoc(self,
                                                        fixed_file):
        """Predicates on generated columns (Record_Id etc.) have no
        copybook field to push against — the documented contract is a
        correct post-hoc filter, never a crash."""
        dset = dataset(fixed_file, generate_record_id="true",
                       **FIXED_OPTS)
        t = dset.to_table(filter=pc.field("Record_Id") < 5)
        assert t["Record_Id"].to_pylist() == [0, 1, 2, 3, 4]
        assert dset.count_rows(filter=pc.field("Record_Id") < 5) == 5

    def test_multifile_batches_match_table_record_identity(self,
                                                           tmp_path):
        """to_batches must agree with to_table on File_Id/Record_Id for
        multi-file datasets (per-file reads would restart both at 0)."""
        paths = []
        for i in range(2):
            p = str(tmp_path / f"part{i}.dat")
            with open(p, "wb") as f:
                f.write(bytes(generate_transactions(50, seed=40 + i)))
            paths.append(p)
        dset = dataset(paths, generate_record_id="true", **FIXED_OPTS)
        via_table = dset.to_table()
        via_batches = pa.Table.from_batches(list(dset.to_batches()))
        assert via_batches.equals(via_table)
        assert sorted(set(via_table["File_Id"].to_pylist())) == [0, 1]


# -- explain --------------------------------------------------------------

class TestExplain:
    def test_prescan_reports_pruning(self):
        from cobrix_tpu.explain import explain

        rep = explain(copybook_contents=EXP3_COPYBOOK,
                      select="COMPANY_ID",
                      filter="segment('C') and TAXPAYER_TYPE == 'A'",
                      **{k: v for k, v in VRL_OPTS.items()
                         if k != "copybook_contents"})
        pd = rep.as_dict()["pushdown"]
        assert pd["fields_pruned"] > 0
        assert pd["pre_decode_segment_drop"] == ["C"]
        assert pd["stage1_filter_fields"] == ["TAXPAYER_TYPE"]
        assert pd["late_materialized"] == ["TAXPAYER_TYPE"]
        assert "pushdown" in rep.render()

    def test_postscan_carries_measured_counters(self, fixed_file):
        rep = read_cobol(fixed_file, filter="CURRENCY == 'USD'",
                         explain=True, **FIXED_OPTS)
        d = rep.as_dict()
        assert d["pushdown"]["measured"]["records_pruned"] > 0
        assert "measured:" in rep.render()


# -- streaming follow (filtered change streams) --------------------------

class TestFollowFiltered:
    def test_follow_subscription_filters_appended_batches(self,
                                                          tmp_path):
        from cobrix_tpu.serve import ScanServer, stream_scan

        path = str(tmp_path / "grow.dat")
        first = bytes(generate_transactions(200, seed=21))
        with open(path, "wb") as f:
            f.write(first)
        srv = ScanServer().start()
        try:
            with hard_timeout(240, "filtered follow"):
                with stream_scan(
                        srv.address, path,
                        filter="CURRENCY == 'USD'",
                        follow={"poll_interval_s": 0.2,
                                "idle_timeout_s": 6.0,
                                "max_batches": 64},
                        **FIXED_OPTS) as s:
                    batches = []
                    appended = False
                    for batch in s:
                        batches.append(batch)
                        if not appended:
                            appended = True
                            with open(path, "ab") as f:
                                f.write(bytes(
                                    generate_transactions(200, seed=22)))
                table = pa.Table.from_batches(batches)
            full = read_cobol(path, **FIXED_OPTS).to_arrow()
            expect = _posthoc(full, pc.equal(full["CURRENCY"], "USD"))
            # the subscription saw both the initial file and the
            # appended tail, filtered — a true change stream
            assert table.num_rows == expect.num_rows
            assert sorted(table["COMPANY_ID"].to_pylist()) == \
                sorted(expect["COMPANY_ID"].to_pylist())
        finally:
            srv.stop()


def test_filtered_read_first_in_fresh_interpreter(vrl_file):
    """Regression: a filtered VRL read as the FIRST read of a process
    (empty per-copybook decoder cache) crashed resolving the stage-1
    decoder cache — in-suite reads share the parse cache, so only a
    fresh interpreter sees the empty-dict state."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from cobrix_tpu import read_cobol\n"
        "from cobrix_tpu.testing.generators import EXP3_COPYBOOK\n"
        "d = read_cobol(%r, copybook_contents=EXP3_COPYBOOK,\n"
        "    is_record_sequence='true', segment_field='SEGMENT_ID',\n"
        "    schema_retention_policy='collapse_root',\n"
        "    redefine_segment_id_map='STATIC-DETAILS => C',\n"
        "    **{'redefine-segment-id-map:1': 'CONTACTS => P'},\n"
        "    filter=\"segment('C') and COMPANY_ID != ''\")\n"
        "print(len(d))\n" % (repo, vrl_file))
    with hard_timeout(180, "fresh-interpreter filtered read"):
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=170,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    assert int(proc.stdout.strip()) > 0


# -- querycheck smoke (the execution grid stays behind `slow`) -----------

def test_querycheck_quick():
    import subprocess
    import sys

    with hard_timeout(420, "querycheck quick"):
        proc = subprocess.run(
            [sys.executable, "tools/querycheck.py", "--mb", "1"],
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.slow
def test_querycheck_sweep():
    import subprocess
    import sys

    with hard_timeout(900, "querycheck sweep"):
        proc = subprocess.run(
            [sys.executable, "tools/querycheck.py", "--mb", "4",
             "--sweep"],
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            capture_output=True, text=True, timeout=880)
    assert proc.returncode == 0, proc.stdout + proc.stderr
