"""Profiling hooks: JAX profiler traces + structured read metrics.

The reference's observability is SLF4J logging around the scan
(CobolScanners.scala:51, IndexBuilder.scala:216 — per-partition offsets
and index counts). The TPU-native equivalents here:

- `profile_trace(dir)`: a context manager wrapping any read/decode in a
  `jax.profiler.trace` session — the artifact opens in TensorBoard/XProf
  and shows the fused kernel, transfers, and collectives on the device
  timeline. The bench writes one such artifact per run.
- `annotate(name)`: named TraceAnnotation spans used inside the decode
  paths (visible on the profiler timeline; ~free when no trace is on).
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
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional


@contextlib.contextmanager
def profile_trace(output_dir: str):
    """Capture a JAX profiler trace of everything inside the block into
    `output_dir` (TensorBoard-loadable)."""
    import jax

    with jax.profiler.trace(output_dir):
        yield


def annotate(name: str):
    """Named span on the profiler timeline; ~free outside a trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


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

    @contextlib.contextmanager
    def timed(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self.add(name, t1 - t0)
            if self.tracer is not None:
                self.tracer.record_span(name, "stage", t0, t1)

    def as_dict(self) -> Dict[str, float]:
        with self._lock:
            return {k: round(v, 6) for k, v in self.busy_s.items()}


def timed_stage(stage_times: Optional[StageTimes], name: str):
    """`stage_times.timed(name)` or a no-op when no accumulator is wired
    (sequential reads pass None through the reader hot paths)."""
    if stage_times is None:
        return contextlib.nullcontext()
    return stage_times.timed(name)


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
    padded batch shape, bytes over the link each way, the seconds spent
    compiling, the devices the outputs lived on, and what kind of program
    ran (does it hold the fused kernel; was that kernel interpreted).
    The record a caller needs to tell a read that used the chip from one
    that only says so. Shared like PassCounters: scan threads reach it
    through the ObsContext."""

    def __init__(self):
        self._lock = threading.Lock()
        self.launches: Dict[tuple, int] = {}
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.compile_s = 0.0
        self.compiles = 0
        self.devices: set = set()
        self.has_kernel: Optional[bool] = None
        self.interpreted: Optional[bool] = None

    def note_launch(self, shape: tuple, h2d_bytes: int, d2h_bytes: int,
                    devices, program, built, interpreted) -> None:
        """One program launch. `program` is the ops.device.CompiledShape
        that ran, `built` whether this launch had to compile it."""
        with self._lock:
            self.launches[shape] = self.launches.get(shape, 0) + 1
            self.h2d_bytes += h2d_bytes
            self.d2h_bytes += d2h_bytes
            self.devices.update(str(d) for d in devices)
            if built:
                self.compiles += 1
                self.compile_s += program.compile_s
            # every launch of the read must agree before the read may
            # claim the kernel: one launch without it turns the flag off
            self.has_kernel = (program.has_kernel if self.has_kernel is None
                               else self.has_kernel and program.has_kernel)
            if interpreted is not None:
                self.interpreted = bool(self.interpreted) or interpreted

    def as_dict(self) -> dict:
        with self._lock:
            return {
                "launches": {f"{b}x{e}": n for (b, e), n
                             in sorted(self.launches.items())},
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "devices": sorted(self.devices),
                "has_kernel": self.has_kernel,
                "interpreted": self.interpreted,
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
            out["device"] = self.device_stats.as_dict()
        roof = self.roofline()
        if roof is not None:
            out["roofline"] = roof
        if self.spans is not None:
            out["span_count"] = len(self.spans)
        return out


class _Stage:
    def __init__(self, metrics: ReadMetrics, name: str):
        self.metrics = metrics
        self.name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        # locked accumulation: the pipelined executor runs stages of the
        # same read on multiple threads, and a bare dict read-modify-write
        # here loses increments under that interleaving
        self.metrics.add_timing(self.name, t1 - self._t0)
        tracer = self.metrics.tracer
        if tracer is not None:
            tracer.record_span(self.name, "phase", self._t0, t1)


def stage(metrics: Optional[ReadMetrics], name: str):
    """Accumulating wall-clock timer for one pipeline stage."""
    if metrics is None:
        return contextlib.nullcontext()
    return _Stage(metrics, name)
