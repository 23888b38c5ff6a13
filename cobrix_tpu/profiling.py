"""Profiling hooks: one stage primitive, JAX profiler traces, structured
read metrics.

The reference's observability is SLF4J logging around the scan
(CobolScanners.scala:51, IndexBuilder.scala:216 — per-partition offsets
and index counts). What stands in for it here, and on which clock:

- `Stage` (through `timed_stage`, `stage`, `StageTimes.timed`): the one
  way to time a block. A single `with` feeds three sinks:
  (a) a `jax.profiler.TraceAnnotation` named ``cobrix.<stage>`` — on the
  PROFILER's clock, beside the device's operations in an `.xplane.pb`
  (only in a process that has already imported JAX; a host-kernel read
  never imports it for this);
  (b) the read's `DeviceStats.stage_s` / `stage_n` — always on, on
  `time.perf_counter`, SELF time: a thread-local stack pauses the
  parent while a child stage runs, so the stages of one thread never
  overlap. Where several threads of one read are inside stages at once
  (a shard pool, pooled table builds, the pipeline's stage threads) each
  instant is split evenly among them (`DeviceStats.stage_clock`), and
  the thread that only waits for them (`PoolWait`) takes none: the
  stages of a read add up to the part of its wall that some stage
  covered, never to more;
  (c) the `StageTimes` busy sums, `ReadMetrics.timings_s` and the
  `obs.Tracer` span where those are attached — on `perf_counter`, whole
  (inclusive) durations, as before.
- `d2h_wait.ready`, `d2h_wait.copy`: the two stages inside `d2h_wait`
  at the one fetch site (`ColumnarDecoder._fetch_block`). The copies
  home are queued first (`jax.copy_to_host_async`, in `d2h_wait`'s own
  time, as `device_get` alone queues them: they start when the outputs
  exist). `d2h_wait.ready`, a plain `Stage`, is `jax.block_until_ready`
  and nothing else: the rest of the H2D copy, the program's run and the
  waiting thread's wake-up. `d2h_wait.copy` (`LinkCopy`) is what is left
  of the bytes' way home once the thread is awake. `LinkCopy` feeds the
  link's own counts in `DeviceStats` besides (`d2h_copy_thread_s`,
  `d2h_copy_busy_s`): what a fetching thread sat through and how long the
  link was in use, which a share of the wall cannot say; beside them
  `d2h_strided_bytes`, the fetched bytes that came home in another order
  than C's and wait for a transposing copy on the host. `plan_index` has
  two children the same way, plain stages, on the thread that cuts the
  index: `plan_index.scan` (the header scan) and `plan_index.seg_ids`
  (every record's segment id). On the route of most files
  (`VarLenReader.generate_index_fast`) they run once a file, over the
  whole image and before any shard starts, `.seg_ids` only for a cut at
  roots. On the route of a dense RDW file (`frame_index_fast`, chosen by
  `reader.index.preframed_route`) the pass is the file's one framing:
  the two run once a window, `.scan` is the fused walk that a shard's
  `frame` stage would have made, `.seg_ids` decodes the ids the shards
  need, and shards run while `plan_index` is still open, so the three
  split the stage clock with them.
- `annotate(name)`: a bare span on the profiler's clock under exactly
  `name` (``cobrix_decode`` round the launch loop: the benchmark's trace
  reduction reads it); ~free when no trace is on.
- `profile_trace(dir)`: a context manager wrapping any read/decode in a
  `jax.profiler.trace` session — the artifact opens in TensorBoard/XProf
  and shows the fused kernel, transfers, and the spans above on one
  timeline.
- `ReadMetrics`: per-read structured counters (files, shards, records,
  bytes, per-stage timings) attached to every CobolData as `.metrics`.
- `StageTimes`: thread-safe per-stage BUSY time accumulation for the
  pipelined execution engine (cobrix_tpu.engine) — wall time alone cannot
  attribute a pipeline win, because overlapped stages each burn close to
  the full wall on a busy pool; busy/wall is the overlap factor.

The host-side scan timeline (trace spans, Chrome-trace export, metrics
registry, live progress) lives in `cobrix_tpu.obs`; ReadMetrics carries
its per-read artifacts (`spans`, `plan_cache` via a per-read cache
scope) and publishes read totals into the default registry.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from .obs import context as obs_context

# the prefix of every stage's span on the profiler's timeline
SPAN_PREFIX = "cobrix."

# the clock of sinks (b) and (c); a module attribute so the self-time
# arithmetic can be tested against a fake one
_clock = time.perf_counter
# per thread: the stages open on it, innermost last
_open = threading.local()


@contextlib.contextmanager
def profile_trace(output_dir: str):
    """Capture a JAX profiler trace of everything inside the block into
    `output_dir` (TensorBoard-loadable)."""
    import jax

    with jax.profiler.trace(output_dir):
        yield


def annotate(name: str):
    """Named span on the profiler timeline; ~free outside a trace. A
    no-op in a process that has not imported JAX: nothing can be
    profiling it, and a span is no reason to pay for the import."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name)


class Stage:
    """One timed block of a read, fed to every sink that is attached
    (module docstring). `stats` is the read's DeviceStats: given by the
    caller for work after the read (the reference a DecodedBatch
    captured), else taken from the thread's obs context."""

    __slots__ = ("name", "stats", "stage_times", "metrics", "_t0", "_at0",
                 "_children", "_joined", "_span")

    def __init__(self, name: str, stats=None, stage_times=None,
                 metrics=None):
        self.name = name
        self.stats = stats
        self.stage_times = stage_times
        self.metrics = metrics

    def __enter__(self):
        stats = self.stats
        if stats is None:
            ctx = obs_context.current()
            if ctx is not None:
                stats = self.stats = ctx.device_stats
        self._span = annotate(SPAN_PREFIX + self.name)
        self._span.__enter__()
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        self._joined = self._joins(
            any(frame.stats is stats for frame in stack))
        stack.append(self)
        self._children = 0.0
        self._t0 = now = _clock()
        self._at0 = (now if stats is None
                     else stats.stage_clock(now, self._joined))
        return self

    def __exit__(self, *exc):
        t0, t1 = self._t0, _clock()
        stats = self.stats
        shared = (t1 if stats is None
                  else stats.stage_clock(t1, -self._joined)) - self._at0
        stack = _open.stack
        stack.pop()
        if stack:
            stack[-1]._children += shared
        self._record(t0, t1, shared - self._children)
        self._span.__exit__(*exc)
        return False

    def _joins(self, inside: bool) -> int:
        """+1 where this stage makes its thread one of those that share
        the read's wall (`inside`: an enclosing stage already did)."""
        return 0 if inside else 1

    def _record(self, t0: float, t1: float, self_s: float) -> None:
        if self.stats is not None:
            self.stats.add_stage(self.name, self_s)
        stage_times, metrics = self.stage_times, self.metrics
        if stage_times is not None:
            stage_times.add(self.name, t1 - t0)
            if stage_times.tracer is not None:
                stage_times.tracer.record_span(self.name, "stage", t0, t1)
        if metrics is not None:
            # locked accumulation: the pipelined executor runs stages of
            # the same read on multiple threads
            metrics.add_timing(self.name, t1 - t0)
            if metrics.tracer is not None:
                metrics.tracer.record_span(self.name, "phase", t0, t1)


class PoolWait(Stage):
    """The block in which a thread only waits for the read's other
    threads (a shard pool, the pooled table builds, the pipeline's stage
    threads). It pauses the stage it sits in and takes no share of the
    wall, so that the workers' stages split it among themselves; counted
    nowhere, but a `cobrix.pool_wait` span on the profiler's clock, which
    `benchmark/stage_gaps.py` reads the same way."""

    __slots__ = ()

    def __init__(self, stats=None):
        super().__init__("pool_wait", stats)

    def _joins(self, inside: bool) -> int:
        return -1 if inside else 0

    def _record(self, t0, t1, self_s) -> None:
        pass


class LinkCopy(Stage):
    """The stage `d2h_wait.copy`: a thread brings a launch's outputs,
    which exist on the device, home over the link: what is left of the
    copies once it is awake. Its whole seconds, not split with the read's
    other threads, go to `DeviceStats.d2h_copy_thread_s` too, and while
    at least one thread of the read is in here `d2h_copy_busy_s` runs."""

    __slots__ = ()

    def __init__(self, stats=None):
        super().__init__("d2h_wait.copy", stats)

    def __enter__(self):
        super().__enter__()
        if self.stats is not None:
            self.stats.copy_clock(self._t0, 1)
        return self

    def _record(self, t0, t1, self_s) -> None:
        super()._record(t0, t1, self_s)
        if self.stats is not None:
            self.stats.copy_clock(t1, -1, t1 - t0)


class StageTimes:
    """Per-stage busy-time accumulator shared by pipeline worker threads.

    `busy_s[stage]` is the SUM of time any thread spent inside that stage
    (read / frame / decode / assemble), so with N-way overlap the busy
    total exceeds the pipeline wall time — the ratio is the overlap
    factor reported in ReadMetrics. A plain dict read-modify-write races
    across threads; the lock makes each accumulation atomic."""

    __slots__ = ("_lock", "busy_s", "tracer")

    def __init__(self, tracer=None):
        self._lock = threading.Lock()
        self.busy_s: Dict[str, float] = {}
        # optional obs.Tracer: when set, every timed stage also lands on
        # the scan timeline as a span (parent = the thread's current
        # chunk/shard span). None costs one attribute check per stage.
        self.tracer = tracer

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self.busy_s[name] = self.busy_s.get(name, 0.0) + seconds

    def timed(self, name: str) -> Stage:
        return Stage(name, stage_times=self)

    def as_dict(self) -> Dict[str, float]:
        with self._lock:
            return {k: round(v, 6) for k, v in self.busy_s.items()}


def timed_stage(stage_times: Optional[StageTimes], name: str) -> Stage:
    """The stage `name` of the read this thread works for. `stage_times`
    is the pipelined engine's accumulator; None on sequential reads,
    where the read's DeviceStats and the profiler still get the stage."""
    return Stage(name, stage_times=stage_times)


class PassCounters:
    """Thread-safe named counters for native-pass accounting.

    Each increment records that one fused native kernel launch actually
    engaged (`fused_frame`, `fused_assembly`, `string_transcode`,
    `take_elided`, ...). asmcheck's quick mode asserts on these so a
    silent fallback to the multi-pass shape fails loudly instead of
    reading as a slowdown. Shared by reference: read-time threads reach
    it through the ObsContext, and post-read Arrow assembly through the
    reference each DecodedBatch captured at decode time (the same
    capture pattern field-cost attribution uses — sequential reads
    assemble Arrow after read_cobol returned and the context died)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}

    def incr(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def as_dict(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


class DeviceStats:
    """What the device decode plane did for one read: program launches by
    padded batch shape, the records they decoded (padding excluded) and
    the rows they were launched as (`launch_rows`, padding included:
    `records` over it is the launches' fill), bytes over the link each
    way, the link home by its own counts (`d2h_*`, below), the seconds
    spent
    compiling (`compile_s`, of which `lower_s` tracing and lowering), the
    devices the outputs lived on, what kind of program ran (does it hold
    the fused kernel; was that kernel interpreted; how many kernel groups
    of the decoders it launched took which route: `device_groups`), and
    the read's
    seconds and entries by stage (`stage_s`, `stage_n`: self time, each
    instant split among the threads inside stages; profiling.Stage), and
    what became of the batches that came with segment row masks: how
    many launched by redefine (`partitioned_batches`), how many the
    plan's widths kept whole (`declined_batches`), and the rows launched
    by set (`set_rows`: a redefine's name, "" for the rows under none;
    columnar.ColumnarDecoder.decode_raw), and for a device aggregate
    (`query_*`, present only then): its chunks, those of them the host
    answered, the rows scanned and passed by the predicate, the groups
    of the result; and for a read of variable-size OCCURS records
    (`odo_*`, present only then; ops/expand.py): the regions of the plans
    it ran, the records laid to the static layout in batches
    (`odo_records`), the bytes their shifts moved them by in all
    (`odo_shifted_bytes`: with `h2d_bytes`, what an expansion on the host
    would have sent more), and the records the row path walked instead
    (`odo_fallback_records`); where an array of variable arrays is read
    by element rows (reader/element_rows.py), the records cut into them
    (`odo_nested_records`), the elements (`odo_elements`) and the
    records the record walk took (`odo_nested_fallback_records`); and for the threaded indexed scan (present
    only where it ran; api._scan_var_len): the shards it planned
    (`index_shards`), the files whose split `reader.index.index_split`
    cut to the pool (`pool_split_files`), the shards that got their
    records' tables from the index pass (`preframed_shards`) and those
    that framed themselves (`self_framed_shards`); and for a hierarchical
    read (`hier_*`, present only then; reader/hierarchical_arrow.py): the
    root rows `hierarchical_table` assembled (`hier_roots`), the records
    it assembled them from (`hier_records`), the child structs it put
    under a parent (`hier_children`), the child records that found none
    (`hier_orphans`), the leaves it built at a struct's rows
    (`hier_leaves_at`) and those it built at full length and took
    (`hier_leaves_taken`), the roots the record walk assembled because the
    columnar assembly declined (`hier_row_path_roots`) and why the last
    of them did (`hier_decline_reason`).
    The link home, noted where a launch is fetched (`ColumnarDecoder.
    _fetch_block`; `LinkCopy`): `d2h_copy_thread_s`, the fetching
    threads' own `perf_counter` seconds bringing ready outputs home,
    summed over launches and threads (`d2h_bytes` over it is the rate a
    fetching thread sees); `d2h_copy_busy_s`, the wall seconds in which
    at least one thread was copying (the link's busy time; the thread
    seconds over it, how many fetch at once); `d2h_strided_bytes`, the
    fetched bytes that did not arrive C-contiguous, which the host has
    still to transpose in `merge`, `collect` or an `assemble.*` stage.
    The record a caller needs to tell a read that used the chip from one
    that only says so. Shared like PassCounters: scan threads reach it
    through the ObsContext."""

    def __init__(self):
        self._lock = threading.Lock()
        self.launches: Dict[tuple, int] = {}
        self.records = 0
        self.launch_rows = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # the link home (LinkCopy, note_launch)
        self.d2h_copy_thread_s = 0.0
        self.d2h_copy_busy_s = 0.0
        self.d2h_strided_bytes = 0
        self._copy_threads = 0
        self._copy_clock_at = 0.0
        self.compile_s = 0.0
        self.lower_s = 0.0
        self.compiles = 0
        self.devices: set = set()
        self.has_kernel: Optional[bool] = None
        self.interpreted: Optional[bool] = None
        # what the launches of kernel programs have said so far
        self._kernel_held: Optional[bool] = None
        self.partitioned_batches = 0
        self.declined_batches = 0
        self.set_rows: Dict[str, int] = {}
        # a device aggregate's chunks (parallel/query.DeviceAggregator)
        self.query_chunks = 0
        self.query_fallback_chunks = 0
        self.query_rows_scanned = 0
        self.query_rows_passed = 0
        self.query_groups = 0
        # variable-size OCCURS records (ColumnarDecoder._note_expansion,
        # VarLenReader.read_result_columnar)
        self.odo_regions = 0
        self.odo_records = 0
        self.odo_fallback_records = 0
        self.odo_shifted_bytes = 0
        # an array of variable arrays read by element rows
        # (reader/element_rows.py)
        self.odo_nested_records = 0
        self.odo_elements = 0
        self.odo_nested_fallback_records = 0
        # index shards of the threaded scan (api._scan_var_len); None:
        # the scan planned none
        self.index_shards: Optional[int] = None
        self.pool_split_files = 0
        self.preframed_shards = 0
        self.self_framed_shards = 0
        # hierarchical assembly (hierarchical_arrow.hierarchical_table,
        # VarLenReader.read_result_columnar)
        self.hier_roots = 0
        self.hier_records = 0
        self.hier_children = 0
        self.hier_orphans = 0
        self.hier_leaves_at = 0
        self.hier_leaves_taken = 0
        self.hier_row_path_roots = 0
        self.hier_decline_reason: Optional[str] = None
        # route counts of every decode program launched, by identity: a
        # read launches one decoder's program many times
        self._program_groups: Dict[int, Dict[str, int]] = {}
        # seconds and entries by stage (profiling.Stage)
        self.stage_s: Dict[str, float] = {}
        self.stage_n: Dict[str, int] = {}
        # the stage clock: seconds of the read's wall in which some
        # thread was inside a stage, advancing by 1/threads of a second
        # per second, so that the threads of a pool split each instant
        self._stage_threads = 0
        self._stage_clock_s = 0.0
        self._stage_clock_at = 0.0

    def stage_clock(self, now: float, joined: int) -> float:
        """The stage clock's reading at the instant `now`, as a thread
        joins (+1) or leaves (-1) the threads that are inside a stage of
        this read, or does neither (0: a nested stage)."""
        with self._lock:
            if self._stage_threads > 0:
                self._stage_clock_s += ((now - self._stage_clock_at)
                                        / self._stage_threads)
            self._stage_clock_at = now
            self._stage_threads += joined
            return self._stage_clock_s

    def add_stage(self, name: str, self_s: float) -> None:
        """One pass through the stage `name`, `self_s` of it (on the stage
        clock) outside any child stage."""
        with self._lock:
            self.stage_s[name] = self.stage_s.get(name, 0.0) + self_s
            self.stage_n[name] = self.stage_n.get(name, 0) + 1

    def copy_clock(self, now: float, joined: int,
                   thread_s: float = 0.0) -> None:
        """`d2h_copy_busy_s` brought up to the instant `now`, at which a
        thread starts (+1) copying a launch's outputs home or is done
        (-1) after `thread_s` of it: the seconds in which at least one
        was, by the arithmetic of `stage_clock`."""
        with self._lock:
            if self._copy_threads > 0:
                self.d2h_copy_busy_s += now - self._copy_clock_at
            self._copy_clock_at = now
            self._copy_threads += joined
            self.d2h_copy_thread_s += thread_s

    def note_launch(self, shape: tuple, records: int, h2d_bytes: int,
                    d2h_bytes: int, devices, program, built,
                    interpreted, device_groups=None,
                    d2h_strided_bytes: int = 0) -> None:
        """One program launch of `records` rows padded to `shape`.
        `program` is the ops.device.CompiledShape that ran, `built`
        whether this launch had to compile it, `device_groups` the route
        counts of the DeviceProgram it belongs to, `d2h_strided_bytes`
        those of `d2h_bytes` that came home in another order than C's."""
        with self._lock:
            self.d2h_strided_bytes += d2h_strided_bytes
            if device_groups is not None:
                self._program_groups[id(device_groups)] = device_groups
            self.launches[shape] = self.launches.get(shape, 0) + 1
            self.records += records
            self.launch_rows += shape[0]
            self.h2d_bytes += h2d_bytes
            self.d2h_bytes += d2h_bytes
            self.devices.update(str(d) for d in devices)
            if built:
                self.compiles += 1
                self.compile_s += program.compile_s
                self.lower_s += program.lower_s
            # every launch of a program that was built round the Pallas
            # kernel must hold it before the read may claim the kernel:
            # one such launch without it turns the flag off for good. A
            # program with nothing for the kernel to take (`interpreted`
            # None: backend "jax", or a set of rows whose groups are all
            # strings) has no say: without a kernel program the answer
            # is no
            if interpreted is not None:
                self._kernel_held = (program.has_kernel
                                     and self._kernel_held is not False)
                self.interpreted = bool(self.interpreted) or interpreted
            self.has_kernel = bool(self._kernel_held)

    def note_partition(self, set_rows: Optional[Dict[str, int]]) -> None:
        """One batch that came with segment row masks: the rows of each
        set it launched, or None where it stayed whole."""
        with self._lock:
            if set_rows is None:
                self.declined_batches += 1
                return
            self.partitioned_batches += 1
            for name, rows in set_rows.items():
                self.set_rows[name] = self.set_rows.get(name, 0) + rows

    def note_query_chunk(self, rows: int, passed: int,
                         fallback: bool) -> None:
        """One chunk of a device aggregate: `rows` scanned, `passed` by
        the predicate; `fallback`: the host answered it, because the
        device could not prove its partials exact."""
        with self._lock:
            self.query_chunks += 1
            self.query_fallback_chunks += bool(fallback)
            self.query_rows_scanned += rows
            self.query_rows_passed += passed

    def note_odo(self, regions: int = 0, records: int = 0,
                 fallback_records: int = 0, shifted_bytes: int = 0) -> None:
        """One batch of variable-size OCCURS records: `records` expanded
        under a plan of `regions` regions, their shifts `shifted_bytes`
        in all; or `fallback_records` walked by the row path."""
        with self._lock:
            self.odo_regions = max(self.odo_regions, regions)
            self.odo_records += records
            self.odo_fallback_records += fallback_records
            self.odo_shifted_bytes += shifted_bytes

    def note_nested(self, records: int, elements: int,
                    fallback_records: int) -> None:
        """One shard of an array of variable arrays: `records` cut into
        element rows, `elements` of them; `fallback_records` walked."""
        with self._lock:
            self.odo_nested_records += records
            self.odo_elements += elements
            self.odo_nested_fallback_records += fallback_records

    def note_plan(self, shards: int, pool_split_files: int) -> None:
        """The threaded scan's plan: `shards` index shards, of files of
        which `pool_split_files` were cut to the pool."""
        with self._lock:
            self.index_shards = shards
            self.pool_split_files = pool_split_files

    def note_shard(self, preframed: bool) -> None:
        """One index shard of the threaded scan: `preframed`, it got its
        records' tables from the index pass; else it framed itself."""
        with self._lock:
            if preframed:
                self.preframed_shards += 1
            else:
                self.self_framed_shards += 1

    def note_hier(self, roots: int = 0, records: int = 0,
                  children: int = 0, orphans: int = 0,
                  leaves_at: int = 0, leaves_taken: int = 0,
                  row_path_roots: int = 0,
                  reason: Optional[str] = None) -> None:
        """One shard of a hierarchical read: `roots` rows assembled in
        columns from `records` records, `children` child structs put
        under a parent and `orphans` child records left under none, a
        struct's leaves built at its rows (`leaves_at`) or at full
        length and taken (`leaves_taken`); or `row_path_roots` roots
        left to the record walk, for `reason`."""
        with self._lock:
            self.hier_roots += roots
            self.hier_records += records
            self.hier_children += children
            self.hier_orphans += orphans
            self.hier_leaves_at += leaves_at
            self.hier_leaves_taken += leaves_taken
            self.hier_row_path_roots += row_path_roots
            if reason is not None:
                self.hier_decline_reason = reason

    @property
    def hier(self) -> dict:
        """The `hier_*` counts, or {} for a read that assembled no
        hierarchical row."""
        with self._lock:
            if not (self.hier_records or self.hier_row_path_roots):
                return {}
            counts = {"hier_roots": self.hier_roots,
                      "hier_records": self.hier_records,
                      "hier_children": self.hier_children,
                      "hier_orphans": self.hier_orphans,
                      "hier_leaves_at": self.hier_leaves_at,
                      "hier_leaves_taken": self.hier_leaves_taken,
                      "hier_row_path_roots": self.hier_row_path_roots}
            if self.hier_decline_reason is not None:
                counts["hier_decline_reason"] = self.hier_decline_reason
            return counts

    @property
    def odo(self) -> Dict[str, int]:
        """The `odo_*` counts, or {} for a read that met no variable-size
        OCCURS record; the three `odo_nested_*` / `odo_elements` counts
        only for a read by element rows."""
        with self._lock:
            nested = {} if not (self.odo_nested_records
                                or self.odo_nested_fallback_records) else {
                "odo_nested_records": self.odo_nested_records,
                "odo_elements": self.odo_elements,
                "odo_nested_fallback_records":
                    self.odo_nested_fallback_records}
            if not (self.odo_records or self.odo_fallback_records
                    or nested):
                return {}
            return {"odo_regions": self.odo_regions,
                    "odo_records": self.odo_records,
                    "odo_fallback_records": self.odo_fallback_records,
                    "odo_shifted_bytes": self.odo_shifted_bytes, **nested}

    @property
    def device_groups(self) -> Dict[str, int]:
        """Kernel groups by the route they took on the device (fused
        Pallas kernel, and how many of those with the batch's rows in
        the lanes; static slices; XLA gather), summed over the decoders
        whose programs this read launched; and, where one of them hands
        back a matrix of EBCDIC code points, `points_u8`: how many do
        in 8 bits a code point."""
        with self._lock:
            counted = list(self._program_groups.values())
        routes = ["fused", "fused_rows_in_lanes", "sliced", "gathered"]
        if any("points_u8" in groups for groups in counted):
            routes.append("points_u8")
        return {route: sum(groups.get(route, 0) for groups in counted)
                for route in routes}

    def as_dict(self) -> dict:
        device_groups = self.device_groups
        odo = self.odo
        hier = self.hier
        with self._lock:
            query = {} if not self.query_chunks else {
                "query_chunks": self.query_chunks,
                "query_fallback_chunks": self.query_fallback_chunks,
                "query_rows_scanned": self.query_rows_scanned,
                "query_rows_passed": self.query_rows_passed,
                "query_groups": self.query_groups}
            shards = {} if self.index_shards is None else {
                "index_shards": self.index_shards,
                "pool_split_files": self.pool_split_files,
                "preframed_shards": self.preframed_shards,
                "self_framed_shards": self.self_framed_shards}
            return {
                **query,
                **odo,
                **hier,
                **shards,
                "device_groups": device_groups,
                "launches": {f"{b}x{e}": n for (b, e), n
                             in sorted(self.launches.items())},
                "records": self.records,
                "launch_rows": self.launch_rows,
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "d2h_copy_thread_s": round(self.d2h_copy_thread_s, 9),
                "d2h_copy_busy_s": round(self.d2h_copy_busy_s, 9),
                "d2h_strided_bytes": self.d2h_strided_bytes,
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "lower_s": round(self.lower_s, 3),
                "devices": sorted(self.devices),
                "has_kernel": self.has_kernel,
                "interpreted": self.interpreted,
                "partitioned_batches": self.partitioned_batches,
                "declined_batches": self.declined_batches,
                "set_rows": dict(sorted(self.set_rows.items())),
                "stage_s": {k: round(v, 6) for k, v
                            in sorted(self.stage_s.items())},
                "stage_n": dict(sorted(self.stage_n.items())),
            }


@dataclass
class ReadMetrics:
    """Structured per-read metrics (the IndexBuilder/CobolScanners log
    lines as data instead of log text)."""

    files: int = 0
    shards: int = 0
    records: int = 0
    bytes_read: int = 0
    backend: str = ""
    hosts: int = 1
    timings_s: Dict[str, float] = field(default_factory=dict)
    # pipelined execution: per-stage busy times (thread-summed) and the
    # executor's shape/overlap report ({workers, chunks, max_inflight,
    # peak_inflight, wall_s, busy_s, overlap}); None on sequential reads
    stage_busy: Optional[StageTimes] = None
    pipeline: Optional[dict] = None
    # distributed supervision events (multihost scheduler / pipeline
    # watchdog): re-dispatches, speculation won/wasted, timeouts, worker
    # deaths; None when the read ran unsupervised
    supervision: Optional[dict] = None
    # compile-cache activity DURING this read (copybook parse / field-plan
    # / code-page LUT hits and misses). Counted through a per-read
    # CacheStatsScope that every thread working for the read activates
    # (obs.context), so concurrent read_cobol calls attribute their own
    # lookups exactly — never each other's
    plan_cache: Optional[dict] = None
    # remote-storage io counters (block/index cache hits, prefetch
    # utilization, bytes fetched — cobrix_tpu.io); None when the read
    # never touched the io layer
    io: Optional[dict] = None
    # finished obs.Tracer span records when the read traced (trace_file
    # or an explicitly attached tracer); None otherwise
    spans: Optional[list] = None
    # query-pushdown pruning counters (records_scanned/records_pruned
    # by depth, bytes_skipped, selectivity — query/pushdown.
    # PushdownStats.as_dict); None when the read carried no filter.
    # In-process executions only: multihost workers prune in their own
    # processes and their counters stay there
    pushdown: Optional[dict] = None

    def __post_init__(self):
        from .io.stats import IoStats
        from .plan.cache import CacheStatsScope

        self._timings_lock = threading.Lock()
        self.cache_scope = CacheStatsScope()
        # per-read remote-IO counter bag, activated alongside the cache
        # scope on every thread working for this read (obs.context)
        self.io_stats = IoStats()
        # optional obs.Tracer for the read (set by read_cobol when
        # tracing is on); stage() timers double as scan-level spans
        self.tracer = None
        # per-field/kernel-group cost attribution
        # (obs.fieldcost.FieldCostAccumulator) — set by read_cobol when
        # the `field_costs` option (or explain=True) enables it; None
        # keeps every attribution timer site a no-op. Snapshots are
        # taken LIVE (field_costs/as_dict), not frozen at finalize:
        # sequential reads assemble Arrow after the read returns, and
        # the snapshot must include that work like the pipelined path's
        self.field_costs_acc = None
        # fused-native-pass engagement counters (always on — one locked
        # dict increment per kernel launch, nowhere near hot-loop cost)
        self.pass_counts = PassCounters()
        # what the device decode plane did (backend jax/pallas); stays
        # empty on host-kernel reads
        self.device_stats = DeviceStats()
        # root-span args dict + trace destination, kept so lazy
        # post-read assembly can fold its costs back into an already
        # written trace artifact (refresh_trace_field_costs)
        self._trace_root_args = None
        self._trace_file = ""

    def add_timing(self, name: str, seconds: float) -> None:
        """Accumulate wall time for one named stage. Locked: pipelined
        reads hit the same metrics object from multiple stage threads."""
        with self._timings_lock:
            self.timings_s[name] = (self.timings_s.get(name, 0.0)
                                    + seconds)

    def finalize(self, data, shards: int) -> None:
        """Attach this metrics object to a finished CobolData."""
        self.shards = max(self.shards, shards)
        self.records = len(data)
        self.plan_cache = dict(self.cache_scope.stats)
        if not self.io_stats.is_zero:
            self.io = self.io_stats.as_dict()
            self.io["prefetch_utilization"] = round(
                self.io_stats.prefetch_utilization, 3)
        if self.tracer is not None:
            root_args = {
                "files": self.files, "shards": self.shards,
                "records": self.records, "bytes": self.bytes_read,
                "backend": self.backend, "hosts": self.hosts}
            fc = self.field_costs
            if fc:
                # the trace artifact carries the cost table too, so
                # `tools/traceview.py --fields` works on a trace file
                # alone, no separate metrics dump needed
                root_args["field_costs"] = fc
            self.tracer.finish_root(args=root_args)
            self._trace_root_args = root_args
            self.spans = list(self.tracer.spans)
        self._publish_registry()
        data.metrics = self

    def refresh_trace_field_costs(self) -> None:
        """Fold the now-complete cost table back into the trace artifact.

        Sequential reads assemble Arrow (and transcode lazy strings)
        AFTER finalize wrote the trace, so a string-heavy traced read
        would otherwise ship a trace whose field_costs is missing or
        missing its assemble plane. Called from `to_arrow` when both
        attribution and `trace_file` were on: the root-span args dict is
        shared by reference with the recorded span, so updating it and
        rewriting (atomic) brings the artifact up to date. No-op for
        untraced / unattributed reads and safely repeatable."""
        if (self.tracer is None or not self._trace_file
                or self._trace_root_args is None):
            return
        fc = self.field_costs
        if not fc:
            return
        self._trace_root_args["field_costs"] = fc
        self.spans = list(self.tracer.spans)
        try:
            self.tracer.write_chrome_trace(self._trace_file)
        except OSError:
            import logging

            logging.getLogger(__name__).warning(
                "failed to refresh trace_file %r with field costs",
                self._trace_file, exc_info=True)

    @property
    def field_costs(self) -> Optional[dict]:
        """Live per-field cost table ({field -> kernel/decode_s/
        assemble_s/bytes/values}); None when attribution is off or
        nothing was attributed yet."""
        acc = self.field_costs_acc
        if acc is None or acc.is_zero:
            return None
        return acc.as_dict()

    def roofline(self) -> Optional[dict]:
        """Achieved scan bytes/s anchored to the calibrated host memory
        bandwidth (obs.roofline); None until both a calibration and a
        finished 'scan' timing exist. Never triggers a calibration."""
        from .obs.roofline import roofline_summary

        scan_s = self.timings_s.get("scan", 0.0)
        return roofline_summary(self.bytes_read, scan_s)

    def _publish_registry(self) -> None:
        """Fold this read into the process-global metrics registry
        (obs.metrics.default_registry): scan/bytes/records totals plus
        the read's cache events, so a Prometheus scrape sees the fleet
        aggregate without touching per-read objects."""
        from .obs.metrics import scan_metrics

        m = scan_metrics()
        m["scans"].inc()
        m["bytes"].inc(self.bytes_read)
        m["records"].inc(self.records)
        for key, count in (self.plan_cache or {}).items():
            if count:
                cache, _, result = key.rpartition("_")
                m["cache"].labels(cache=cache, result=result).inc(count)
        io = self.io or {}
        for plane in ("block", "index", "compress"):
            for result, label in (("hits", "hit"), ("misses", "miss")):
                count = io.get(f"{plane}_{result}", 0)
                if count:
                    m["io_cache"].labels(
                        plane=plane, result=label).inc(count)
            corrupt = io.get(f"{plane}_corrupt", 0)
            if corrupt:
                # detections ride IoStats during a read (multihost
                # workers merge theirs home) and reach Prometheus here,
                # exactly once per detection
                m["cache_corruption"].labels(plane=plane).inc(corrupt)
        for result, label in (("issued", "issued"), ("hits", "hit"),
                              ("waits", "wait"), ("unused", "unused")):
            count = io.get(f"prefetch_{result}", 0)
            if count:
                m["prefetch"].labels(result=label).inc(count)
        if io.get("bytes_fetched"):
            m["remote_bytes"].labels(source="backend").inc(
                io["bytes_fetched"])
        if io.get("bytes_from_cache"):
            m["remote_bytes"].labels(source="cache").inc(
                io["bytes_from_cache"])
        if io.get("compressed_bytes_in"):
            m["inflate_bytes"].labels(direction="in").inc(
                io["compressed_bytes_in"])
        if io.get("decompressed_bytes_out"):
            m["inflate_bytes"].labels(direction="out").inc(
                io["decompressed_bytes_out"])
        if io.get("inflate_s"):
            m["inflate_seconds"].inc(io["inflate_s"])
        if io.get("inflate_skipped"):
            m["inflate_skipped"].inc(io["inflate_skipped"])
        if io.get("bytes_from_peer"):
            # peer-tier EVENTS are counted live by PeerCacheTier; here
            # only the byte volume joins the backend/cache split
            m["remote_bytes"].labels(source="peer").inc(
                io["bytes_from_peer"])
        pd = self.pushdown or {}
        for depth in ("segment", "filter", "residual"):
            count = pd.get(f"records_pruned_{depth}", 0)
            if count:
                m["records_pruned"].labels(depth=depth).inc(count)
        if pd.get("bytes_skipped"):
            m["bytes_skipped"].inc(pd["bytes_skipped"])
        if pd.get("chunks_skipped"):
            m["chunks_skipped"].inc(pd["chunks_skipped"])
        roof = self.roofline()
        if roof is not None:
            m["roofline"].set(roof["fraction"])

    def as_dict(self) -> dict:
        out = {
            "files": self.files,
            "shards": self.shards,
            "records": self.records,
            "bytes_read": self.bytes_read,
            "backend": self.backend,
            "hosts": self.hosts,
            "timings_s": {k: round(v, 6) for k, v in self.timings_s.items()},
        }
        if self.stage_busy is not None:
            out["stage_busy_s"] = self.stage_busy.as_dict()
        if self.pipeline is not None:
            out["pipeline"] = self.pipeline
        if self.supervision is not None:
            out["supervision"] = self.supervision
        if self.plan_cache is not None:
            out["plan_cache"] = self.plan_cache
        if self.io is not None:
            out["io"] = self.io
        if self.pushdown is not None:
            out["pushdown"] = self.pushdown
        fc = self.field_costs
        if fc is not None:
            out["field_costs"] = fc
        passes = self.pass_counts.as_dict()
        if passes:
            out["native_passes"] = passes
        if self.device_stats.launches:
            out["device_groups"] = self.device_stats.device_groups
            out["device"] = self.device_stats.as_dict()
        odo = self.device_stats.odo
        if odo:
            # on every backend: the host kernels and the row path launch
            # nothing and still say what they did with the regions
            out["odo"] = odo
        hier = self.device_stats.hier
        if hier:
            out["hier"] = hier
        roof = self.roofline()
        if roof is not None:
            out["roofline"] = roof
        if self.spans is not None:
            out["span_count"] = len(self.spans)
        return out


def stage(metrics: Optional[ReadMetrics], name: str) -> Stage:
    """Accumulating wall-clock timer for one phase of a read
    (`timings_s[name]`, whole durations), counted as a stage too."""
    return Stage(name,
                 stats=metrics.device_stats if metrics is not None
                 else None,
                 metrics=metrics)
