"""The stage primitive (profiling.Stage) and where it is applied along the
device read path: self time on a thread-local stack, the always-on
counters in DeviceStats, the spans on the profiler's clock, and no JAX in
a host-kernel process. The exp1 half (a minute of interpret-mode compile)
lives in test_stage_tracing_exp1.py, a file and so a worker of its own."""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cobrix_tpu import profiling, read_cobol
from cobrix_tpu.obs import context as obs_context
from cobrix_tpu.profiling import (DeviceStats, LinkCopy, PoolWait,
                                  ReadMetrics, Stage, StageTimes, stage,
                                  timed_stage)
from cobrix_tpu.reader import columnar
from cobrix_tpu.testing.generators import (EXP2_COPYBOOK, EXP3_COPYBOOK,
                                           generate_exp2, generate_exp3)

from util import check_stage_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXP3_OPTIONS = dict(
    is_record_sequence="true", segment_field="SEGMENT-ID",
    redefine_segment_id_map="STATIC-DETAILS => C",
    redefine_segment_id_map_1="CONTACTS => P",
    copybook_contents=EXP3_COPYBOOK)

# every stage of a sequential multisegment read on a device backend that
# launches more than one block
EXP3_STAGES = {
    "parse_copybook", "plan_index", "scan", "read", "frame", "decode",
    "pack", "h2d", "launch", "d2h_wait", "d2h_wait.ready", "d2h_wait.copy",
    "merge", "collect", "to_arrow", "assemble.table", "assemble.list",
    "assemble.scalar", "assemble.string"}


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def tick(self, seconds):
        self.now += seconds


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(profiling, "_clock", fake)
    return fake


def test_nested_stages_give_self_time(clock):
    stats = DeviceStats()
    with Stage("outer", stats):
        clock.tick(1.0)
        with Stage("inner", stats):
            clock.tick(2.0)
            with Stage("leaf", stats):
                clock.tick(4.0)
        with Stage("inner", stats):
            clock.tick(8.0)
        clock.tick(16.0)
    assert stats.stage_s == {"outer": 17.0, "inner": 10.0, "leaf": 4.0}
    assert stats.stage_n == {"outer": 1, "inner": 2, "leaf": 1}
    # stages of one thread never overlap: their sum is the wall
    assert sum(stats.stage_s.values()) == 31.0
    assert stats.as_dict()["stage_s"] == {"inner": 10.0, "leaf": 4.0,
                                          "outer": 17.0}


def test_a_raising_block_is_counted_and_unwinds_the_stack(clock):
    stats = DeviceStats()
    with pytest.raises(KeyError):
        with Stage("outer", stats):
            with Stage("inner", stats):
                clock.tick(1.0)
                raise KeyError("x")
    assert stats.stage_s == {"inner": 1.0, "outer": 0.0}
    with Stage("next", stats):
        clock.tick(2.0)
    assert stats.stage_s["next"] == 2.0   # no parent left on the stack


def test_one_with_feeds_every_sink(clock):
    """StageTimes and timings_s keep whole durations (what they meant
    before); DeviceStats gets self time; a child without a sink of its own
    still pauses its parent."""
    metrics = ReadMetrics()
    times = StageTimes()
    ctx = obs_context.ObsContext(device_stats=metrics.device_stats)
    with obs_context.activate(ctx):
        with stage(metrics, "scan"):
            clock.tick(1.0)
            with times.timed("decode"):
                clock.tick(2.0)
                with timed_stage(times, "h2d"):
                    clock.tick(4.0)
    assert metrics.timings_s == {"scan": 7.0}
    assert times.busy_s == {"decode": 6.0, "h2d": 4.0}
    assert metrics.device_stats.stage_s == {"scan": 1.0, "decode": 2.0,
                                            "h2d": 4.0}


class OtherThread:
    """A thread that runs what it is handed, one call at a time, so that a
    test can open and close stages on it at instants of the fake clock."""

    def __init__(self):
        import queue

        self.calls = queue.Queue()
        self.done = queue.Queue()
        self.thread = threading.Thread(target=self.run, daemon=True)
        self.thread.start()

    def run(self):
        for call in iter(self.calls.get, None):
            call()
            self.done.put(True)

    def __call__(self, call):
        self.calls.put(call)
        assert self.done.get(timeout=10)

    def stop(self):
        self.calls.put(None)
        self.thread.join(10)
        assert not self.thread.is_alive()


def test_threads_inside_stages_split_each_instant_of_the_wall(clock):
    stats = DeviceStats()
    other = OtherThread()
    a, b = Stage("a", stats), Stage("b", stats)
    a.__enter__()                                    # t = 0
    clock.tick(2.0)
    other(b.__enter__)                               # t = 2
    clock.tick(4.0)
    other(lambda: b.__exit__(None, None, None))      # t = 6
    clock.tick(4.0)
    a.__exit__(None, None, None)                     # t = 10
    other.stop()
    # 2..6 is shared by two threads: each takes half of it
    assert stats.stage_s == {"a": 8.0, "b": 2.0}
    assert sum(stats.stage_s.values()) == 10.0       # the wall


def test_a_thread_waiting_for_its_pool_takes_no_share(clock):
    stats = DeviceStats()
    first, second = OtherThread(), OtherThread()
    decode = [Stage("decode", stats), Stage("decode", stats)]
    with Stage("scan", stats):                       # t = 0
        clock.tick(1.0)
        with PoolWait(stats):                        # t = 1
            first(decode[0].__enter__)
            second(decode[1].__enter__)
            clock.tick(2.0)
            second(lambda: decode[1].__exit__(None, None, None))    # 3
            clock.tick(2.0)
            first(lambda: decode[0].__exit__(None, None, None))     # 5
        clock.tick(1.0)                              # t = 6
    first.stop()
    second.stop()
    # 1..3 split between two workers, 3..5 one worker's; the caller's
    # scan is paused while it waits and counts 0..1 and 5..6
    assert stats.stage_s == {"decode": 4.0, "scan": 2.0}
    assert stats.stage_n == {"decode": 2, "scan": 1}
    assert sum(stats.stage_s.values()) == 6.0        # the wall


def test_d2h_wait_with_its_children_is_what_it_was(clock):
    """The fetch's two halves are stages beneath `d2h_wait`: the three
    add up to the block (queuing the copies is the block's own time), and
    the copy's whole seconds are the thread's."""
    stats = DeviceStats()
    with Stage("d2h_wait", stats):
        clock.tick(0.25)
        with Stage("d2h_wait.ready", stats):
            clock.tick(2.0)
        with LinkCopy(stats):
            clock.tick(4.0)
        clock.tick(0.5)
    assert stats.stage_s == {"d2h_wait": 0.75, "d2h_wait.ready": 2.0,
                             "d2h_wait.copy": 4.0}
    assert stats.stage_n == {"d2h_wait": 1, "d2h_wait.ready": 1,
                             "d2h_wait.copy": 1}
    said = stats.as_dict()
    assert (said["d2h_copy_thread_s"], said["d2h_copy_busy_s"],
            said["d2h_strided_bytes"]) == (4.0, 4.0, 0)


def test_two_threads_copying_at_once_keep_the_link_busy_once(clock):
    stats = DeviceStats()
    other = OtherThread()
    mine, theirs = LinkCopy(stats), LinkCopy(stats)
    with Stage("d2h_wait.ready", stats):             # t = 0
        clock.tick(3.0)
    mine.__enter__()                                 # t = 3
    other(theirs.__enter__)
    clock.tick(1.0)
    other(lambda: theirs.__exit__(None, None, None))  # t = 4
    mine.__exit__(None, None, None)
    clock.tick(5.0)                                  # nobody copies
    with LinkCopy(stats):                            # t = 9
        clock.tick(0.5)
    other.stop()
    # 3..4 held two threads: two thread-seconds, one busy second
    assert stats.d2h_copy_thread_s == 2.5
    assert stats.d2h_copy_busy_s == 1.5
    # the wait for the chip is a stage like any other: no count of its own
    assert stats.stage_s["d2h_wait.ready"] == 3.0
    # the stage clock split 3..4 between them, as it does any stage
    assert stats.stage_s["d2h_wait.copy"] == 1.5


def test_a_copy_that_raises_leaves_the_link_idle(clock):
    stats = DeviceStats()
    with pytest.raises(KeyError):
        with LinkCopy(stats):
            clock.tick(1.0)
            raise KeyError("x")
    clock.tick(8.0)
    with LinkCopy(stats):
        clock.tick(2.0)
    assert stats.d2h_copy_busy_s == stats.d2h_copy_thread_s == 3.0


def fortran(shape, dtype):
    return np.asfortranarray(np.zeros(shape, dtype))


@pytest.mark.parametrize("leaf,strided", [
    (fortran((6, 4), np.int32), 6 * 4 * 4),          # rows-minor
    (np.zeros((6, 4), np.int32), 0),                 # as C lays it
    (fortran((6, 1), np.int64), 0),                  # one column
    (fortran((1, 6), np.bool_), 0),                  # one row
    (np.zeros((8, 6), np.uint8)[:, :4], 8 * 4),      # a cut of the rows
    (np.zeros((), np.int32), 0),                     # a scalar
], ids=["fortran", "c", "one_column", "one_row", "sliced", "scalar"])
def test_strided_bytes_count_a_leaf_that_is_not_c_contiguous_whole(
        leaf, strided):
    assert columnar.strided_nbytes([leaf]) == strided
    # a launch's count is the sum over its leaves
    both = [leaf, fortran((3, 5), np.uint8)]
    assert columnar.strided_nbytes(both) == strided + 15


def test_note_launch_adds_the_strided_bytes_up():
    class Program:
        has_kernel = True

    stats = DeviceStats()
    for strided in (100, 0, 28):
        stats.note_launch((256, 64), 200, 256 * 64, 512, (), Program(),
                          False, False, d2h_strided_bytes=strided)
    said = stats.as_dict()
    assert (said["d2h_strided_bytes"], said["d2h_bytes"]) == (128, 1536)


def test_concurrent_add_stage_loses_no_update():
    stats = DeviceStats()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def hammer():
            for _ in range(2000):
                with Stage("h2d", stats):
                    pass
                with LinkCopy(stats):
                    pass

        threads = [threading.Thread(target=hammer) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert stats.stage_n == {"h2d": 32000, "d2h_wait.copy": 32000}
    # every thread that began a copy ended it: the busy clock stands
    assert stats._copy_threads == 0
    assert 0.0 < stats.d2h_copy_busy_s <= stats.d2h_copy_thread_s


# enough 'C' records (a third of them) to fill more than one block of 256
RECORDS = 1000


@pytest.fixture(scope="module")
def exp3_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("exp3") / "exp3.bin"
    path.write_bytes(bytes(generate_exp3(RECORDS, seed=24)))
    return str(path)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 256 wide rows, so that a few hundred 'C' records launch
    several and their outputs have to be merged."""
    monkeypatch.setattr(columnar, "DEVICE_BLOCK_BYTES", 1 << 20)


def test_exp3_read_counts_every_stage(exp3_file, small_blocks):
    t0 = time.perf_counter()
    data = read_cobol(exp3_file, backend="pallas", **EXP3_OPTIONS)
    table = data.to_arrow()
    wall_s = time.perf_counter() - t0
    metrics = data.metrics.as_dict()
    device = metrics["device"]
    assert table.num_rows == len(data) > 256
    assert sum(device["launches"].values()) >= 2
    check_stage_record(device, wall_s, EXP3_STAGES)
    if device["compiles"]:
        assert device["stage_n"]["compile"] == device["compiles"]
    # timings_s keeps whole durations, to_arrow among them now
    assert metrics["timings_s"]["to_arrow"] > 0
    assert metrics["timings_s"]["scan"] >= device["stage_s"]["scan"]
    # a second .to_arrow() accumulates
    data.to_arrow()
    again = data.metrics.as_dict()
    assert again["timings_s"]["to_arrow"] > metrics["timings_s"]["to_arrow"]
    assert again["device"]["stage_n"]["to_arrow"] == 2
    # one slot leaf of the OCCURS is not a stage of its own
    assert device["stage_n"]["assemble.scalar"] < 100
    # the one batch's list came from the decoded planes (NUM1 and NUM2),
    # not slot by slot
    assert device["stage_n"]["assemble.list"] == 1
    assert metrics["native_passes"]["plane_list"] == 2
    assert again["device"]["stage_n"]["assemble.list"] == 2
    assert again["native_passes"]["plane_list"] == 4
    assert "assemble.list.slots" not in again["device"]["stage_s"]
    # the one batch launched by redefine, and says which rows went where
    assert device["partitioned_batches"] == 1
    assert device["declined_batches"] == 0
    assert set(device["set_rows"]) == {"STATIC_DETAILS", "CONTACTS"}
    assert sum(device["set_rows"].values()) == device["records"] == RECORDS
    assert device["set_rows"]["STATIC_DETAILS"] > 256


def test_a_fetch_queues_its_copies_then_waits_then_brings_them_home(
        exp3_file, small_blocks, monkeypatch):
    """The one fetch site's three calls into the runtime, each in the
    stage that is meant to hold it: the copies home are queued in
    `d2h_wait`'s own time, so that `d2h_wait.ready` is the wait for the
    outputs and nothing else, and `d2h_wait.copy` what is left of their
    way home."""
    import jax

    seen = []

    def spy(name):
        real = getattr(jax, name)

        def call(*args, **kwargs):
            stack = profiling._open.stack
            seen.append((name, stack[-1].name if stack else None))
            return real(*args, **kwargs)

        monkeypatch.setattr(jax, name, call)

    for name in ("copy_to_host_async", "block_until_ready", "device_get"):
        spy(name)
    data = read_cobol(exp3_file, backend="pallas", **EXP3_OPTIONS)
    launches = sum(data.metrics.as_dict()["device"]["launches"].values())
    assert launches >= 2
    assert seen == [("copy_to_host_async", "d2h_wait"),
                    ("block_until_ready", "d2h_wait.ready"),
                    ("device_get", "d2h_wait.copy")] * launches


def test_a_sharded_read_splits_the_wall_among_its_threads(exp3_file):
    """Shards scanned by a thread pool and tables built by one: every
    instant is split among the threads inside stages, the waiting caller
    takes none, so the stages still add up to at most the wall."""
    t0 = time.perf_counter()
    data = read_cobol(exp3_file, backend="pallas", parallelism="4",
                      input_split_records=str(RECORDS // 4), **EXP3_OPTIONS)
    table = data.to_arrow()
    wall_s = time.perf_counter() - t0
    metrics = data.metrics.as_dict()
    assert table.num_rows == RECORDS and metrics["shards"] == 4
    device = metrics["device"]
    check_stage_record(device, wall_s,
                       (EXP3_STAGES | {"plan_index.scan"}) - {"merge"})
    # one header scan of the whole file cut the shards; no Seg_Id level
    # asks for a cut at roots
    assert device["stage_n"]["plan_index.scan"] == 1
    assert "plan_index.seg_ids" not in device["stage_s"]
    assert device["stage_n"]["decode"] == 4
    assert device["stage_n"]["assemble.list"] == 4
    assert metrics["native_passes"]["plane_list"] == 2 * 4
    assert "assemble.list.slots" not in device["stage_s"]
    # whole durations, summed over the pool's threads, may pass the wall;
    # the shared clock may not
    assert "pool_wait" not in device["stage_s"]


def test_a_list_of_strings_is_built_slot_by_slot(tmp_path):
    """What the plane route cannot serve runs under a stage of its own,
    beneath `assemble.list`."""
    path = tmp_path / "strings.bin"
    path.write_bytes(b"\xc1\xc2\xc3\xc4\xc5\xc6" * 20)
    data = read_cobol(str(path), backend="jax", copybook_contents="""
       01 R.
          05 S OCCURS 3 PIC X(2).
    """)
    assert data.to_arrow().column("R")[0].as_py() == {"S": ["AB", "CD", "EF"]}
    metrics = data.metrics.as_dict()
    device = metrics["device"]
    assert device["stage_n"]["assemble.list.slots"] == 1
    assert device["stage_n"]["assemble.list"] == 1
    assert "plane_list" not in metrics["native_passes"]
    # a fixed-length read plans no index
    assert not any(name.startswith("plan_index") for name in
                   device["stage_s"])
    # a fixed-length read brings no row masks: nothing to partition,
    # nothing declined
    assert (device["partitioned_batches"], device["declined_batches"],
            device["set_rows"]) == (0, 0, {})


@pytest.mark.parametrize("route", ["index_whole", "framed_once"])
@pytest.mark.parametrize("backend", ["pallas", "numpy"])
def test_plan_index_counts_its_header_scan_and_its_segment_ids(
        tmp_path, monkeypatch, backend, route):
    """A multisegment RDW file cut at roots: the header scan and the
    segment ids are stages beneath `plan_index` whatever kernels decode
    the shards, and on both routes of the indexed scan: once a file where
    the index is planned whole and every shard frames itself; once a
    window, and the ids only where a root is looked for, where the pass
    is the file's one framing (exp2's 64 B records choose that route
    themselves; no record is short enough once the constant is 0)."""
    from cobrix_tpu.reader import index, var_len_reader

    if route == "index_whole":
        monkeypatch.setattr(index, "DENSE_MAX_MEAN_RECORD", 0)
    else:
        # windows of a few hundred records, not the file in one
        monkeypatch.setattr(var_len_reader, "INDEX_WINDOW_SLACK", 512)
    path = tmp_path / "companies.dat"
    path.write_bytes(generate_exp2(1300, seed=34))
    data = read_cobol(
        str(path), backend=backend, copybook_contents=EXP2_COPYBOOK,
        is_record_sequence="true", segment_field="SEGMENT-ID",
        redefine_segment_id_map="STATIC-DETAILS => C",
        redefine_segment_id_map_1="CONTACTS => P", segment_id_level0="C",
        segment_id_level1="P", segment_id_prefix="A",
        input_split_records="300")
    shards = data.metrics.shards
    assert shards >= 3
    stats = data.metrics.device_stats
    assert stats.stage_n["plan_index"] == 1
    if route == "index_whole":
        assert stats.stage_n["plan_index.scan"] == 1
        assert stats.stage_n["plan_index.seg_ids"] == 1
        assert stats.stage_n["frame"] == shards
        assert (stats.preframed_shards, stats.self_framed_shards) == (
            0, shards)
    else:
        # a window an entry at the least, a root search a cut
        assert stats.stage_n["plan_index.scan"] >= shards
        assert stats.stage_n["plan_index.seg_ids"] >= shards - 1
        # the shards still code their ids, in `frame`, off the pass
        assert stats.stage_n["frame"] == shards
        assert (stats.preframed_shards, stats.self_framed_shards) == (
            shards, 0)
    # self time: the children are not counted in the parent again, and
    # beside running shards the three take no more than their wall
    beneath = sum(s for name, s in stats.stage_s.items()
                  if name.startswith("plan_index"))
    assert beneath <= data.metrics.timings_s["plan_index"] + 1e-6
    assert set(name for name in stats.stage_s
               if name.startswith("plan_index")) == {
        "plan_index", "plan_index.scan", "plan_index.seg_ids"}


def test_a_pipelined_read_counts_on_its_stage_threads(exp3_file):
    data = read_cobol(exp3_file, backend="pallas", pipeline_workers="2",
                      **EXP3_OPTIONS)
    data.to_arrow()
    metrics = data.metrics.as_dict()
    stage_s = metrics["device"]["stage_s"]
    assert {"read", "frame", "decode", "assemble", "assemble.table",
            "assemble.list", "h2d", "launch", "d2h_wait", "d2h_wait.ready",
            "d2h_wait.copy"} <= set(stage_s)
    busy = metrics["stage_busy_s"]
    # busy seconds are whole durations, the counters self time
    assert busy["decode"] >= stage_s["decode"]
    assert busy["assemble"] >= stage_s["assemble"]


def host_events(trace_dir: str) -> list:
    """(thread line, name, start_ns, end_ns) of the program's spans."""
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("cobrix", "caller.")):
                    events.append((line.name, e.name, e.start_ns,
                                   e.start_ns + e.duration_ns))
    return events


def test_spans_lie_on_the_profilers_clock(exp3_file, small_blocks, tmp_path):
    import jax

    # compile outside the trace
    read_cobol(exp3_file, backend="pallas", **EXP3_OPTIONS).to_arrow()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("caller.read"):
            data = read_cobol(exp3_file, backend="pallas", **EXP3_OPTIONS)
        with jax.profiler.TraceAnnotation("caller.to_arrow"):
            data.to_arrow()
    finally:
        jax.profiler.stop_trace()
    events = host_events(str(tmp_path))
    by_name = {}
    for line, name, start, end in events:
        by_name.setdefault(name, []).append((line, start, end))

    def inside(name, outer):
        return all(any(ol == line and os_ <= start and end <= oe
                       for ol, os_, oe in by_name[outer])
                   for line, start, end in by_name[name])

    launches = sum(data.metrics.as_dict()["device"]["launches"].values())
    for name in ("cobrix.h2d", "cobrix.launch", "cobrix.d2h_wait",
                 "cobrix.d2h_wait.ready", "cobrix.d2h_wait.copy"):
        assert len(by_name[name]) == launches
        assert inside(name, "cobrix_decode")
    for name in ("cobrix.d2h_wait.ready", "cobrix.d2h_wait.copy"):
        assert inside(name, "cobrix.d2h_wait")
    # the wait for the chip ends before the copy home begins
    assert all(ready[2] <= copy[1] for ready, copy in zip(
        sorted(by_name["cobrix.d2h_wait.ready"], key=lambda e: e[1]),
        sorted(by_name["cobrix.d2h_wait.copy"], key=lambda e: e[1])))
    assert len(by_name["cobrix_decode"]) == 1
    assert inside("cobrix_decode", "cobrix.decode")
    assert inside("cobrix.decode", "cobrix.scan")
    for name in ("cobrix.read", "cobrix.frame", "cobrix.pack",
                 "cobrix.merge", "cobrix.collect", "cobrix.scan"):
        assert inside(name, "caller.read"), name
    for name in ("cobrix.assemble.table", "cobrix.assemble.list",
                 "cobrix.assemble.scalar", "cobrix.assemble.string"):
        assert inside(name, "caller.to_arrow"), name
    assert inside("cobrix.assemble.list", "cobrix.assemble.table")
    # all on the caller's line: a sequential read has one thread
    assert len({line for line, _, _, _ in events}) == 1


def test_lowered_exp3_program_carries_the_scopes(exp3_file):
    import jax

    data = read_cobol(exp3_file, backend="pallas", **EXP3_OPTIONS)
    decoder = data._results[0].segments[0].batch.decoder
    program = decoder.device_program()
    text = program._jit.lower(jax.ShapeDtypeStruct(
        (256, decoder.plan.max_extent), np.uint8)).as_text(debug_info=True)
    for scope in ("cobrix.planes", "cobrix.kernel", "cobrix.outputs"):
        assert scope in text, scope


HOST_READ = """
import json, sys
sys.path.insert(0, sys.argv[1])
from cobrix_tpu import read_cobol
from cobrix_tpu.testing.generators import EXP3_COPYBOOK
data = read_cobol(sys.argv[2], backend="numpy",
                  copybook_contents=EXP3_COPYBOOK, **json.loads(sys.argv[3]))
table = data.to_arrow()
print(json.dumps({"jax": "jax" in sys.modules, "rows": table.num_rows,
                  "stages": sorted(data.metrics.device_stats.stage_s),
                  "device": data.metrics.as_dict().get("device")}))
"""


def test_a_host_kernel_read_never_imports_jax(exp3_file):
    options = {k: v for k, v in EXP3_OPTIONS.items()
               if k != "copybook_contents"}
    # cut into shards, so that the index is planned: its stages too are
    # no reason to import JAX
    options["input_split_records"] = str(RECORDS // 4)
    proc = subprocess.run(
        [sys.executable, "-c", HOST_READ, REPO, exp3_file,
         json.dumps(options)], capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = json.loads(proc.stdout.splitlines()[-1])
    assert said["jax"] is False
    assert said["rows"] > 0
    # the counters ran all the same; a host read shows no device record
    assert {"read", "frame", "decode", "assemble.table", "plan_index",
            "plan_index.scan"} <= set(said["stages"])
    assert not any(name.startswith("d2h_wait") for name in said["stages"])
    assert said["device"] is None


def test_the_serve_trailer_carries_busy_seconds_and_stage_counters(exp3_file):
    from cobrix_tpu.serve import ScanServer, stream_scan

    from util import hard_timeout

    with hard_timeout(120):
        server = ScanServer(enable_http=False).start()
        try:
            with stream_scan(server.address, exp3_file, backend="pallas",
                             **EXP3_OPTIONS) as stream:
                table = stream.table()
                metrics = stream.summary["metrics"]
        finally:
            server.stop()
    assert table.num_rows == RECORDS
    busy = metrics["stage_busy_s"]
    assert {"read", "frame", "decode", "assemble"} <= set(busy)
    stage_s = metrics["device"]["stage_s"]
    assert {"h2d", "launch", "d2h_wait", "d2h_wait.ready", "d2h_wait.copy",
            "assemble", "assemble.list"} <= set(stage_s)
    assert busy["assemble"] >= stage_s["assemble"]
    # the link's own counts ride the trailer with the bytes
    device = metrics["device"]
    assert 0.0 < device["d2h_copy_busy_s"] <= device["d2h_copy_thread_s"]
    assert 0 <= device["d2h_strided_bytes"] <= device["d2h_bytes"]
    assert metrics["device"]["lower_s"] <= metrics["device"]["compile_s"]
