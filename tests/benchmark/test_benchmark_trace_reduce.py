"""benchmark/trace_reduce.py on a small recorded trace of a TPU v5e (three
launches of the exp3 decode inside one traced scan, cut from a real
.xplane.pb of PR 23) and on hand-made planes whose answers are known."""
import json
import os

import pytest

from benchmark_testing import REPO  # noqa: F401

from benchmark import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu_v5e_exp3_scan_trace.json")


def planes(device_events, host_events, async_events=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_decode_all(1)", 10, 50]]},
            {"name": "XLA Ops", "events": device_events},
            {"name": "Async XLA Ops", "events": list(async_events)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host_events}]},
    ]


def test_union_clip_and_complement():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [[0, 4], [5, 9]]
    assert tr.clip([[0, 4], [5, 9]], 3, 6) == [[3, 4], [5, 6]]
    assert tr.complement([[3, 4], [5, 6]], 0, 10) == [[0, 3], [4, 5], [6, 10]]
    assert tr.total([[0, 3], [4, 5]]) == 4
    assert tr.short_op_name("%fusion.14 = u16[114688]{0} fusion(...)") \
        == "fusion.14"


def test_busy_window_and_gaps_by_host_span():
    # window 0..100 ns; the device runs 10..30 and 25..40 (overlapping:
    # the union is 10..40) and an async copy 60..70
    reduced = tr.reduce_trace(planes(
        [["%a = f32[] add()", 10, 20], ["%b = f32[] mul()", 25, 15]],
        [[tr.WINDOW_SPAN, 0, 100], ["bench.read_cobol", 0, 50],
         ["cobrix_decode", 5, 40], ["bench.to_arrow", 50, 45]],
        async_events=[["%copy-start = ...", 60, 10]]))
    assert reduced["window_s"] == pytest.approx(100e-9)
    assert reduced["busy_s"] == pytest.approx(40e-9)
    assert reduced["launches"] == {"jit_decode_all": 1}
    assert reduced["device_ops"] == [["a", pytest.approx(20e-9)],
                                     ["b", pytest.approx(15e-9)]]
    gaps = dict(reduced["idle_gaps"])
    assert gaps == {
        "bench.read_cobol/host_outside_decode": pytest.approx(10e-9),
        "bench.read_cobol/cobrix_decode": pytest.approx(10e-9),
        "bench.to_arrow/host_outside_decode": pytest.approx(35e-9),
        "no_bench_span/host_outside_decode": pytest.approx(5e-9)}
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_events_outside_the_window_do_not_count():
    reduced = tr.reduce_trace(planes(
        [["%a = x", 0, 30], ["%b = x", 90, 30]],
        [[tr.WINDOW_SPAN, 20, 80]]))
    assert reduced["busy_s"] == pytest.approx(20e-9)  # 20..30 and 90..100


def test_two_device_planes_average_busy_and_idle_needs_all_idle():
    both = planes([["%a = x", 0, 40]], [[tr.WINDOW_SPAN, 0, 100]])
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["%a = x", 20, 40]]}]}
    reduced = tr.reduce_trace(both + [second])
    assert reduced["devices"] == 2
    assert reduced["busy_s"] == pytest.approx(40e-9)
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(40e-9)


@pytest.mark.parametrize("broken", [
    lambda p: [p[0]],                                  # no host plane
    lambda p: [p[1]],                                  # no device plane
], ids=["no_window_span", "no_device_plane"])
def test_nothing_to_read_returns_nothing(broken):
    full = planes([["%a = x", 0, 10]], [[tr.WINDOW_SPAN, 0, 100]])
    assert tr.reduce_trace(broken(full)) is None


def test_the_recorded_tpu_trace():
    """Three launches of the 8192x16064 exp3 decode, about 12.2 ms each on
    the device, inside the first 0.4 s of read_cobol(): the device is idle
    nearly all the time and the gaps lie under the host's spans."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    reduced = tr.reduce_trace(recorded["planes"])
    want = recorded["expected"]
    assert reduced["launches"] == want["launches"]
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert [name for name, _ in reduced["device_ops"][:3]] == want["top_ops"]
    assert 0.9 < 1 - reduced["busy_s"] / reduced["window_s"] < 1.0
    assert {name.split("/")[0] for name, _ in reduced["idle_gaps"]} <= {
        "bench.read_cobol", "bench.to_arrow", "bench.between_scans",
        tr.NO_SPAN}
    # a launch's operations fill its module event: busy is the launches
    modules = [e for line in recorded["planes"][0]["lines"]
               if line["name"] == "XLA Modules" for e in line["events"]]
    assert reduced["busy_s"] == pytest.approx(
        sum(d for _, _, d in modules) / 1e9, rel=0.01)


def test_load_xplane_reads_a_profile_written_here(tmp_path):
    """The loader on a real .xplane.pb (of the CPU: no device plane, so
    nothing to reduce, but the benchmark's spans are found)."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import Tracer

    tracer = Tracer(True, str(tmp_path))
    tracer.start()
    with tracer.span("bench.read_cobol"):
        jnp.arange(8).sum().block_until_ready()
    tracer.stop()
    loaded = tr.load_xplane(tracer.trace_file())
    names = {e[0] for plane in loaded for line in plane["lines"]
             for e in line["events"]}
    assert {"bench.read_cobol", tr.WINDOW_SPAN} <= names
    assert tr.reduce_trace(loaded) is None  # a CPU has no device plane
    assert jax.devices()[0].platform == "cpu"
