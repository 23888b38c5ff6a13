"""Structured per-read metrics + profiler trace hooks (SURVEY.md §5
tracing/observability rows — the reference only logs these as SLF4J
text, CobolScanners.scala:51 / IndexBuilder.scala:216)."""
import glob
import os

from cobrix_tpu import profile_trace, read_cobol
from cobrix_tpu.testing.generators import (EXP1_COPYBOOK, EXP2_COPYBOOK,
                                           generate_exp1, generate_exp2)

KW = dict(copybook_contents=EXP2_COPYBOOK, is_record_sequence="true",
          segment_field="SEGMENT-ID",
          redefine_segment_id_map="STATIC-DETAILS => C",
          redefine_segment_id_map_1="CONTACTS => P",
          segment_id_prefix="M")


def test_read_metrics_var_len_indexed(tmp_path):
    raw = generate_exp2(4000, seed=3)
    p = tmp_path / "exp2.dat"
    p.write_bytes(raw)
    out = read_cobol(str(p), input_split_records="1000", **KW)
    m = out.metrics
    assert m is not None
    assert m.files == 1
    assert m.shards >= 3           # the sparse index split the file
    assert m.records == len(out) == 4000
    assert m.bytes_read == len(raw)
    assert m.backend == "numpy"
    for key in ("parse_copybook", "plan_index", "scan"):
        assert m.timings_s[key] >= 0.0, key
    d = m.as_dict()
    assert d["records"] == 4000 and "timings_s" in d


def test_read_metrics_fixed_len(tmp_path):
    data = generate_exp1(16, seed=4)
    p = tmp_path / "exp1.dat"
    p.write_bytes(data.tobytes())
    out = read_cobol(str(p), copybook_contents=EXP1_COPYBOOK)
    m = out.metrics
    assert m.files == 1 and m.shards == 1
    assert m.records == 16
    assert m.bytes_read == data.nbytes
    assert "scan" in m.timings_s


def test_read_metrics_multihost(tmp_path):
    raw = generate_exp2(3000, seed=5)
    p = tmp_path / "exp2.dat"
    p.write_bytes(raw)
    out = read_cobol(str(p), hosts="2", input_split_records="800", **KW)
    m = out.metrics
    assert m.hosts == 2
    assert m.shards >= 2
    assert m.records == 3000
    assert m.timings_s["scan"] > 0.0


def test_profile_trace_writes_artifact(tmp_path):
    """A jax.profiler trace wrapping a jax-backend decode produces an
    artifact directory."""
    data = generate_exp1(8, seed=6)
    p = tmp_path / "exp1.dat"
    p.write_bytes(data.tobytes())
    trace_dir = str(tmp_path / "trace")
    with profile_trace(trace_dir):
        out = read_cobol(str(p), copybook_contents=EXP1_COPYBOOK,
                         backend="jax")
        assert len(out) == 8
    produced = glob.glob(os.path.join(trace_dir, "**", "*"),
                         recursive=True)
    assert produced, "no trace artifact written"
