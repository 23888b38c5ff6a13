"""benchmark/stage_gaps.py: the traced window's idle seconds by the
innermost `cobrix.*` stage of the host threads, on hand-made planes whose
answers are known and on a recording of a TPU v5e (the .xplane.pb of one
traced `exp3_read` scan of PR 24, cut down to the device's operations,
their names shortened, and the benchmark's and the program's spans)."""
import json
import os

import pytest

from benchmark_testing import REPO  # noqa: F401

from benchmark import stage_gaps as sg
from benchmark import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tpu_v5e_exp3_stage_spans.json")


def planes(device_events, threads: dict):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": device_events}]},
        {"name": "/host:CPU", "lines": [
            {"name": name, "events": events}
            for name, events in threads.items()]},
    ]


def test_innermost_cuts_nested_spans_into_disjoint_pieces():
    pieces = sg.innermost([("a", 0, 100), ("b", 10, 30), ("c", 15, 20),
                           ("d", 40, 50), ("e", 200, 210), ("f", 7, 7)])
    assert pieces == [(0, 10, "a"), (10, 15, "b"), (15, 20, "c"),
                      (20, 30, "b"), (30, 40, "a"), (40, 50, "d"),
                      (50, 100, "a"), (200, 210, "e")]
    # a child that outlives its parent (clock jitter) breaks nothing
    assert sg.innermost([("a", 0, 10), ("b", 5, 12)]) == [
        (0, 5, "a"), (5, 12, "b")]


def test_idle_seconds_go_to_the_innermost_stage():
    # window 0..100 ns, the device runs 20..30 and 60..70: 80 ns idle
    gaps = sg.stage_gaps(planes(
        [["%a = x", 20, 10], ["%b = x", 60, 10]],
        {"caller": [[tr.WINDOW_SPAN, 0, 100], ["bench.read_cobol", 2, 66],
                    ["cobrix.scan", 5, 60], ["cobrix.h2d", 10, 10],
                    ["cobrix_decode", 8, 50], ["cobrix.d2h_wait", 25, 30],
                    ["cobrix.assemble.table", 70, 25],
                    ["cobrix.assemble.list", 75, 10]],
         "runtime": [["ThreadpoolListener::Record", 0, 90]]}))
    assert gaps["window_s"] == pytest.approx(100e-9)
    assert gaps["idle_s"] == pytest.approx(80e-9)
    assert gaps["threads"] == 1
    assert dict(gaps["by_stage"]) == {
        "cobrix.d2h_wait": pytest.approx(25e-9),      # 30..55
        "cobrix.assemble.table": pytest.approx(15e-9),
        "cobrix.assemble.list": pytest.approx(10e-9),
        "cobrix.scan": pytest.approx(10e-9),          # 5..10 and 55..60
        "cobrix.h2d": pytest.approx(10e-9),
        sg.OUTSIDE_STAGES: pytest.approx(10e-9)}      # 0..5 and 95..100
    assert gaps["under_a_stage"] == pytest.approx(70 / 80)
    assert gaps["by_stage"][0][0] == "cobrix.d2h_wait"     # largest first


def test_threads_of_a_pool_split_each_idle_instant():
    """A caller waiting for two workers takes no share; the workers split
    what they are both in, as the program's stage counters do."""
    gaps = sg.stage_gaps(planes(
        [["%a = x", 20, 10], ["%b = x", 60, 10]],
        {"caller": [[tr.WINDOW_SPAN, 0, 100], ["cobrix.scan", 5, 60],
                    [sg.POOL_WAIT, 10, 50],
                    ["cobrix.assemble.table", 70, 25]],
         "worker-1": [["cobrix.decode", 10, 50], ["cobrix.h2d", 12, 8]],
         "worker-2": [["cobrix.decode", 10, 30]]}))
    assert gaps["threads"] == 3
    assert dict(gaps["by_stage"]) == {
        # 10..12 both, 12..20 half, 30..40 both, 40..60 worker-1 alone
        "cobrix.decode": pytest.approx((2 + 4 + 10 + 20) * 1e-9),
        "cobrix.h2d": pytest.approx(4e-9),            # half of 12..20
        "cobrix.scan": pytest.approx(5e-9),           # 5..10, then it waits
        "cobrix.assemble.table": pytest.approx(25e-9),
        sg.OUTSIDE_STAGES: pytest.approx(10e-9)}
    assert sum(s for _, s in gaps["by_stage"]) == pytest.approx(
        gaps["idle_s"])


@pytest.mark.parametrize("broken", [
    lambda p: [p[0]],
    lambda p: [p[1]],
], ids=["no_window_span", "no_device_plane"])
def test_a_trace_without_device_or_window_gives_nothing(broken, capsys,
                                                        tmp_path):
    whole = planes([["%a = x", 0, 10]],
                   {"caller": [[tr.WINDOW_SPAN, 0, 100]]})
    assert sg.stage_gaps(broken(whole)) is None
    path = tmp_path / "planes.json"
    path.write_text(json.dumps(broken(whole)))
    assert sg.main([str(path)]) == 1
    assert sg.main([]) == 2
    capsys.readouterr()


def test_recorded_tpu_trace_puts_the_idle_seconds_under_stages(capsys):
    recorded = tr.load_json(RECORDED)["planes"]
    reduced = tr.reduce_trace(recorded)
    gaps = sg.stage_gaps(recorded)
    # the same window and the same idle seconds as the accepted reduction
    assert gaps["window_s"] == pytest.approx(reduced["window_s"])
    assert gaps["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert reduced["launches"] == {"jit_decode_all": 16}
    assert gaps["under_a_stage"] > 0.9
    by_stage = dict(gaps["by_stage"])
    assert {"cobrix.h2d", "cobrix.d2h_wait", "cobrix.pack",
            "cobrix.assemble.list"} <= set(by_stage)
    assert sg.POOL_WAIT not in by_stage
    assert sum(by_stage.values()) == pytest.approx(gaps["idle_s"])
    assert sg.main([RECORDED]) == 0
    printed = capsys.readouterr().out
    assert json.loads(printed.splitlines()[0]) == gaps
    assert "cobrix.assemble.list" in printed
