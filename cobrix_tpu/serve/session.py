"""One streamed scan: request -> read_cobol -> ordered Arrow batches.

`ScanSession` owns everything between a parsed request and the emitted
record batches, independent of transport (the TCP frame server and the
optional Flight front-end both drive it):

* option hygiene — client options are the read_cobol option surface,
  minus the server-owned keys (`trace_file` writes server disk,
  `hosts` forks server processes); the server's own option overrides
  (shared `cache_dir`, pipeline defaults) merge on top, so every
  tenant's scans land on the same process-wide block/index/plan caches;
* the streaming tap — the scan runs with `batch_callback`, so on the
  pipelined paths the first batch leaves the server after ONE chunk
  decodes (first-batch latency), not after the whole table exists;
* record order — the tap delivers chunks in completion order; the
  OrderedBatchEmitter re-orders by chunk index so the client's
  concatenated stream is row-identical to `to_arrow()`;
* memory bounds — every buffered-or-being-written byte is charged to
  the tenant's `max_inflight_bytes` via the admission controller's byte
  gate (backpressure, then a structured timeout — never an unbounded
  reorder buffer). Keep the byte budget above the pipeline's in-flight
  window (workers+2 chunks) or the gate can fire on a healthy scan;
* the trailer — rows/batches/bytes, the request's
  `request_id`/`trace_id` echo, the ReadDiagnostics ledger JSON
  (re-attached client-side so streamed tables carry byte-identical
  schema metadata), the read's io/plan-cache metrics, and — when the
  client sent ``trace: true`` — the server-side trace spans + clock
  sample the client merges into its own timeline, so a client can
  assert warm-cache behavior and debug latency without server shell
  access.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from .protocol import ServeError

# option keys a client may NOT set: they reach server-local resources
# (filesystem paths, process topology) that belong to the operator
SERVER_OWNED_OPTIONS = ("trace_file", "cache_dir", "cache_max_mb",
                        "hosts")

# read_cobol parameters the session itself supplies (path positionally,
# the callbacks, the request tracer, and explain's return-type switch):
# a client option with one of these names would raise a confusing
# TypeError deep in the call — or silently change the session's
# contract — instead of a structured protocol rejection here.
# (copybook/copybook_contents/backend stay client-settable: they flow
# through **kwargs into read_cobol's named parameters untouched.)
RESERVED_OPTION_KEYS = ("path", "progress_callback", "batch_callback",
                        "explain", "tracer")

# streaming wants the pipelined engine (that is where first-batch
# latency comes from); a request may still override explicitly
DEFAULT_STREAM_OPTIONS = {"pipeline_workers": "-1"}


# read options that do NOT shape which records stream in which order:
# identity/telemetry, io/cache/prefetch plumbing, retry budgets, and
# engine parallelism knobs (sequential==pipelined==multihost row parity
# is pinned by tests). Excluded from the chunk-plan fingerprint so two
# replicas with different OPERATOR config (cache_dir mount points,
# prefetch depths, worker counts) still accept each other's resume
# tokens — only row-shaping divergence may refuse a resume.
NON_PLAN_OPTIONS = frozenset((
    "trace_id", "request_id", "trace_file", "field_costs",
    "progress_interval_s",
    "cache_dir", "cache_max_mb", "prefetch_blocks", "io_block_mb",
    "io_retry_attempts", "io_retry_base_delay", "io_retry_max_delay",
    "io_retry_deadline",
    "pipeline_workers", "pipeline_chunk_mb", "pipeline_max_inflight",
    "chunk_size_mb", "stream_batch_rows",
    "shard_timeout_s", "shard_max_retries", "speculative_quantile",
    "scan_deadline_s", "heartbeat_interval_s", "hosts",
))


def plan_fingerprint(files: List[str], read_kwargs: dict) -> str:
    """The chunk-plan fingerprint a resume token carries: a digest of
    each input's *content version* (local size+mtime_ns; a backend's
    own fingerprint — etag/ukey — for registry schemes) plus every
    read option that shapes which records stream in which order. Two
    replicas sharing storage compute the SAME fingerprint for the same
    file version, so a client can resume on either; a changed file
    changes the fingerprint and the resume is refused
    (``resume_mismatch``) — a resumed stream must never splice rows of
    two file versions.

    Cost: one stat / backend metadata round trip per file per request,
    before any byte decodes (the read's own memoized probe runs inside
    read_cobol and is not reachable from here). That is the price of
    every stream being resumable; it is the same cost class as the
    scan's own per-read version probe."""
    from ..reader.stream import (normalize_local, path_scheme,
                                 resolve_stream_backend)

    versions = []
    for f in files:
        scheme = path_scheme(f)
        token = "unknown"
        if scheme in (None, "file"):
            try:
                st = os.stat(normalize_local(f))
                token = f"local:{st.st_size}:{st.st_mtime_ns}"
            except OSError:
                token = "absent"
        else:
            try:
                factory = resolve_stream_backend(scheme)
                if factory is not None:
                    source = factory(f)
                    try:
                        token = source.fingerprint()
                    finally:
                        source.close()
            except Exception:
                token = "unprobeable"
        versions.append(f"{f}|{token}")
    opts = {k: v for k, v in read_kwargs.items()
            if k not in NON_PLAN_OPTIONS}
    payload = json.dumps([versions, opts], sort_keys=True, default=str)
    return hashlib.sha256(payload.encode("utf-8")).hexdigest()[:24]


class ScanRequest:
    """Validated request payload (the 'R' frame JSON)."""

    def __init__(self, payload: dict):
        from ..obs.trace import new_trace_id

        files = payload.get("files")
        if not files or not isinstance(files, (list, tuple)):
            raise ServeError("request must carry a non-empty 'files' "
                             "list", code="protocol")
        self.files: List[str] = [str(f) for f in files]
        options = payload.get("options") or {}
        if not isinstance(options, dict):
            raise ServeError("'options' must be an object",
                             code="protocol")
        self.options: Dict[str, object] = dict(options)
        self.tenant = str(payload.get("tenant") or "default")
        max_records = payload.get("max_records")
        self.max_records: Optional[int] = (None if max_records is None
                                           else int(max_records))
        self.want_progress = bool(payload.get("progress"))
        # request-scoped identity: the client mints both ids (so ITS
        # spans and logs already carry them before the server answers);
        # requests from older/bare clients get server-minted ids so the
        # audit record and trace are still addressable
        self.request_id = str(payload.get("request_id") or "") \
            or new_trace_id()[:16]
        self.trace_id = str(payload.get("trace_id") or "") \
            or new_trace_id()
        # client opt-in: ship the server-side trace spans back on the
        # trailer so the client can merge one cross-process Chrome trace
        self.want_trace = bool(payload.get("trace"))
        # follow mode (continuous ingestion): true or an options object
        # ({poll_interval_s, idle_timeout_s, max_batches, batch_max_mb,
        # tail_grace_s, truncation_policy}) — the session becomes a
        # live subscription driven by serve/follow.FollowSession
        follow = payload.get("follow") or False
        if follow not in (False, True) and not isinstance(follow, dict):
            raise ServeError("'follow' must be true or an object",
                             code="protocol")
        self.follow = follow
        self.is_follow = bool(follow)
        # resume of an interrupted stream: {plan, records, of} (+
        # `watermark` for follow subscriptions — the per-source state
        # a replacement replica seeds its ingestor from). `plan`
        # must match this server's computed chunk-plan fingerprint
        # (validated in ScanSession.run), `records` are skipped before
        # anything hits the wire, `of` is the ORIGINAL request_id the
        # audit log ties the attempts together under (resume_of)
        resume = payload.get("resume") or {}
        if resume and not isinstance(resume, dict):
            raise ServeError("'resume' must be an object",
                             code="protocol")
        self.resume_plan = str(resume.get("plan") or "")
        watermark = resume.get("watermark") or {}
        if watermark and not isinstance(watermark, dict):
            raise ServeError("'resume.watermark' must be an object",
                             code="protocol")
        self.resume_watermark = watermark
        try:
            self.resume_records = max(0, int(resume.get("records") or 0))
        except (TypeError, ValueError):
            raise ServeError("'resume.records' must be an integer",
                             code="protocol")
        self.resume_of = str(resume.get("of") or "")
        # only a resume that actually SKIPS records is honored as one:
        # with records=0 nothing was delivered, so the request is an
        # ordinary fresh scan — no plan validation needed (nothing can
        # splice) and, crucially, no resume_of stamp: resumed records
        # are exempt from SLO evaluation, and a zero-cost 'resume'
        # shape must not let a client opt its scans out of SLO
        # accounting (a real resume forfeits at least one record)
        self.is_resume = bool(resume) and self.resume_records > 0

    def read_kwargs(self, server_options: Optional[dict]) -> dict:
        """The effective read_cobol option map: defaults, then client
        options minus server-owned keys, then the operator's overrides
        (the operator always wins — that is what pins every tenant to
        one shared cache_dir)."""
        kw = dict(DEFAULT_STREAM_OPTIONS)
        for key, value in self.options.items():
            if key in SERVER_OWNED_OPTIONS:
                raise ServeError(
                    f"option '{key}' is server-owned and cannot be set "
                    "by a serving client", code="protocol")
            if key in RESERVED_OPTION_KEYS:
                raise ServeError(
                    f"'{key}' is not a string option (it is a "
                    "read_cobol parameter the session controls)",
                    code="protocol")
            kw[key] = value
        kw.update(server_options or {})
        # the request-level ids always win over option-level ones: the
        # triple on the 'R' frame IS the identity the audit log keys on
        kw["trace_id"] = self.trace_id
        kw["request_id"] = self.request_id
        return kw


class OrderedBatchEmitter:
    """Re-orders the batch tap's (chunk_index, table) stream into chunk
    order and forwards each table to `write_table`. Table deliveries
    all arrive on one thread (the pipeline's dedicated assembly thread,
    or the caller's for the fallback path); `(index, None)`
    failed-chunk signals may arrive on OTHER threads and mark the index
    a permanent gap, so buffered later chunks drain instead of pinning
    the byte gate until the scan ends. Gaps discovered only at scan end
    are skipped at `finish()` — either way the emitted rows are exactly
    what `to_arrow()` would return. The byte gate provides cross-scan
    backpressure."""

    # acquire slice while gap-stalled: long enough to not spin, short
    # enough to notice a failed-chunk signal promptly
    _GATE_SLICE_S = 0.5

    def __init__(self, write_table: Callable, tenant: str,
                 controller=None, max_records: Optional[int] = None,
                 skip_records: int = 0):
        self.write_table = write_table
        self.tenant = tenant
        self.controller = controller
        self.max_records = max_records
        # resume support: records already delivered to this client by a
        # previous attempt — dropped here before they reach the wire.
        # Whole tables inside the skip window are discarded without
        # slicing (the cheap path: a resumed scan's already-delivered
        # chunks cost decode but neither Arrow materialization nor
        # serialization nor network), the boundary table is sliced once
        self.skip_records = max(0, int(skip_records))
        self.rows_skipped = 0
        self.rows_emitted = 0
        self.tables_emitted = 0
        self._next = 0
        self._held: Dict[int, object] = {}
        self._held_bytes: Dict[int, int] = {}
        self._done = False
        # indexes that will NEVER emit (failed chunks, partial policy);
        # written cross-thread, hence the lock
        self._skipped = set()
        self._skip_lock = threading.Lock()

    def emit(self, index: int, table) -> None:
        if table is None:
            with self._skip_lock:
                self._skipped.add(index)
            # no flush from this (foreign) thread — the assembly
            # thread's next emit / gate retry / finish() drains
            return
        if self._done:
            return
        nbytes = int(table.nbytes)
        if self.controller is not None:
            self._acquire_gate(nbytes)
        self._held[index] = table
        self._held_bytes[index] = nbytes
        self._flush_ready()

    def _acquire_gate(self, nbytes: int) -> None:
        """Byte-gate acquire that keeps draining: between short waits,
        flush anything a newly-signalled failed chunk unblocked (that
        releases held bytes). Gives up only after the controller's full
        `byte_wait_timeout_s` passes with zero progress — drained bytes
        or an advanced gap both re-arm the clock.

        The wait is for bytes OTHER scans of the tenant hold. This
        buffer's own tables drain only when the chunk it waits for
        arrives, and that chunk comes through this same serialized tap:
        a chunk table can outweigh its input several times (exp3's wide
        records), so the tables that finish ahead of the next one can
        exceed the whole budget, and blocking on them would hold the
        next one out until the timeout failed a healthy scan."""
        window = self.controller.byte_wait_timeout_s
        t0 = time.monotonic()
        last_next = self._next
        last_held = None
        while True:
            self._flush_ready()
            if self._next != last_next:
                last_next = self._next
                t0 = time.monotonic()  # gap progress re-arms the clock
            budget_left = window - (time.monotonic() - t0)
            try:
                self.controller.acquire_bytes(
                    self.tenant, nbytes,
                    timeout_s=min(self._GATE_SLICE_S,
                                  max(0.0, budget_left)),
                    own_bytes=sum(self._held_bytes.values()))
                return
            except TimeoutError as exc:
                held = self.controller.inflight_bytes(self.tenant)
                if last_held is not None and held < last_held:
                    t0 = time.monotonic()  # drain progress, same deal
                last_held = held
                if window - (time.monotonic() - t0) \
                        <= self._GATE_SLICE_S:
                    raise TimeoutError(
                        f"tenant '{self.tenant}' held {held} in-flight "
                        f"bytes for {window:.0f}s with no drain and no "
                        "failed-chunk gap progress (client too slow or "
                        "gone)") from exc

    def _flush_ready(self) -> None:
        while True:
            with self._skip_lock:
                if self._next in self._skipped:
                    self._skipped.discard(self._next)
                    self._next += 1
                    continue
            if self._next not in self._held:
                return
            index = self._next
            table = self._held.pop(index)
            nbytes = self._held_bytes.pop(index)
            try:
                self._write_capped(table)
            finally:
                if self.controller is not None:
                    self.controller.release_bytes(self.tenant, nbytes)
            self._next += 1

    def _write_capped(self, table) -> None:
        if self._done:
            return
        remaining_skip = self.skip_records - self.rows_skipped
        if remaining_skip > 0:
            if table.num_rows <= remaining_skip:
                self.rows_skipped += table.num_rows
                return  # wholly inside the skip window: drop, unsliced
            self.rows_skipped = self.skip_records
            table = table.slice(remaining_skip)
        if self.max_records is not None:
            remaining = self.max_records - self.rows_emitted
            if remaining <= 0:
                self._done = True
                return
            if table.num_rows > remaining:
                table = table.slice(0, remaining)
        if table.num_rows == 0 and self.tables_emitted:
            return  # empty non-first chunks add nothing to the stream
        self.rows_emitted += table.num_rows
        self.tables_emitted += 1
        self.write_table(table)

    def finish(self) -> None:
        """Flush what remains, skipping failed-chunk gaps (buffered
        indexes past a gap emit in ascending order)."""
        for index in sorted(self._held):
            table = self._held.pop(index)
            nbytes = self._held_bytes.pop(index)
            try:
                self._write_capped(table)
            finally:
                if self.controller is not None:
                    self.controller.release_bytes(self.tenant, nbytes)

    def abort(self) -> None:
        """Drop buffered tables and return their bytes to the gate."""
        self._done = True
        self._held.clear()
        if self.controller is not None:
            for nbytes in self._held_bytes.values():
                self.controller.release_bytes(self.tenant, nbytes)
        self._held_bytes.clear()


class ScanSession:
    """Run one admitted request and deliver ordered Arrow tables to
    `write_table`; returns the summary trailer dict. Transport-neutral:
    raising from `write_table` aborts the scan (dead client).

    `tracer`: the request's `obs.Tracer` (trace_id already set from the
    request) — injected into read_cobol so queue-wait and scan spans
    share one timeline; the server's flight recorder and the client's
    merged trace both read it. `force_progress` drives the progress
    callback even when the client didn't opt into 'P' frames (the
    `/debug/scans` live view needs ScanProgress regardless).
    `force_field_costs` turns per-field attribution on server-side so a
    flight-recorder dump carries the cost table."""

    def __init__(self, request: ScanRequest,
                 server_options: Optional[dict] = None,
                 controller=None,
                 on_progress: Optional[Callable] = None,
                 tracer=None,
                 force_progress: bool = False,
                 force_field_costs: bool = False,
                 on_plan: Optional[Callable] = None):
        self.request = request
        # called with the chunk-plan fingerprint BEFORE any decode: the
        # transport ships it as the stream's first resume token, so a
        # client losing the connection at ANY later point knows the
        # plan identity it must resume against
        self.on_plan = on_plan
        self.server_options = server_options
        self.controller = controller
        self.on_progress = on_progress
        self.tracer = tracer
        self.force_progress = force_progress
        self.force_field_costs = force_field_costs
        # the finished scan's ReadMetrics (None until run() succeeds);
        # the flight recorder reads field costs off it. The tracer is
        # caller-owned, so trace evidence survives even a raised scan
        self.metrics = None
        # the result's Arrow schema (set by run): lets the transport
        # send a valid EMPTY IPC stream when a scan produced no batches
        self.result_schema = None
        # resume-token state the transport reads mid-stream: the chunk-
        # plan fingerprint (set before the first batch) and the emitter
        # (its rows_emitted is the live delivery watermark)
        self.plan_fp = ""
        self.emitter: Optional[OrderedBatchEmitter] = None
        # True when memory pressure degraded this scan's io knobs
        self.degraded = False

    def delivered_records(self) -> int:
        """Records delivered to this client so far across ALL attempts:
        the resume token's watermark (prior attempts' skip + this
        attempt's emitted rows)."""
        emitted = self.emitter.rows_emitted if self.emitter else 0
        return self.request.resume_records + emitted

    def resume_token(self) -> dict:
        return {"plan": self.plan_fp,
                "records": self.delivered_records()}

    def run(self, write_table: Callable) -> dict:
        from ..api import read_cobol

        req = self.request
        kwargs = req.read_kwargs(self.server_options)
        if self.force_field_costs:
            # operator-owned, like the ids in read_kwargs: the flight
            # recorder's evidence must not be disableable by a client
            # sending field_costs="false"
            kwargs["field_costs"] = "true"
        # chunk-plan fingerprint: computed up front on EVERY streamed
        # scan (one stat/metadata probe per file) so every resume token
        # carries it, and validated against an inbound resume BEFORE
        # any byte is decoded — a stale file version must fail fast
        # with a structured error, never splice mixed-version rows
        self.plan_fp = plan_fingerprint(req.files, kwargs)
        if req.is_resume and req.resume_plan != self.plan_fp:
            raise ServeError(
                "resume token does not match this server's chunk plan "
                "(the input file(s) or options changed since the "
                "original attempt); restart the scan from record 0",
                code="resume_mismatch")
        # a resumed request's max_records is the ORIGINAL total: this
        # attempt emits only what remains after the already-delivered
        # records are skipped
        max_records = req.max_records
        if max_records is not None:
            max_records = max(0, max_records - req.resume_records)
        emitter = OrderedBatchEmitter(
            write_table, req.tenant, controller=self.controller,
            max_records=max_records, skip_records=req.resume_records)
        self.emitter = emitter
        self._maybe_degrade(kwargs)
        if self.on_plan is not None:
            self.on_plan(self.plan_fp)
        progress_cb = None
        if self.on_progress is not None and (req.want_progress
                                             or self.force_progress):
            progress_cb = self.on_progress
        t0 = time.monotonic()
        try:
            data = read_cobol(req.files if len(req.files) > 1
                              else req.files[0],
                              progress_callback=progress_cb,
                              batch_callback=emitter.emit,
                              tracer=self.tracer, **kwargs)
            emitter.finish()
        except BaseException:
            emitter.abort()
            raise
        from ..reader.arrow_out import arrow_schema

        self.result_schema = arrow_schema(data.schema)
        self.metrics = data.metrics
        diagnostics = (data.diagnostics.to_json()
                       if data.diagnostics is not None else None)
        summary = {
            "rows": emitter.rows_emitted,
            "tables": emitter.tables_emitted,
            "records_total": len(data),
            "scan_s": round(time.monotonic() - t0, 6),
            "request_id": req.request_id,
            "trace_id": req.trace_id,
            "diagnostics": diagnostics,
            # the final recovery watermark: a client that loses the
            # connection AFTER the last data frame but before/while
            # reading this trailer can still resume (and skip
            # everything)
            "resume_token": self.resume_token(),
        }
        if req.is_resume:
            summary["resume_of"] = req.resume_of or req.request_id
            summary["rows_skipped"] = emitter.rows_skipped
        if self.degraded:
            summary["degraded"] = True
        if data.metrics is not None:
            m = data.metrics
            summary["metrics"] = {
                "shards": m.shards,
                "bytes_read": m.bytes_read,
                "plan_cache": m.plan_cache,
                "io": m.io,
                "pipeline": ({"chunks": m.pipeline.get("chunks"),
                              "overlap": m.pipeline.get("overlap")}
                             if m.pipeline else None),
                # busy seconds by pipeline stage, summed over the stage
                # threads (read/frame/decode/assemble; whole durations,
                # so they exceed scan_s where stages overlapped). The
                # self-time split is `device.stage_s`
                "stage_busy_s": (m.stage_busy.as_dict()
                                 if m.stage_busy is not None else None),
                # per-field cost attribution + roofline anchoring: the
                # streaming happened via batch_callback DURING the scan,
                # so the table is complete here — serving clients get
                # "which columns cost what" and "what fraction of the
                # hardware limit" without any server shell access.
                # Client opt-in via the `field_costs` read option; None
                # when attribution was off (the zero-overhead default)
                "field_costs": m.field_costs,
                "roofline": m.roofline(),
                # pruning counters when the request pushed a filter
                # down (records_pruned by depth, bytes_skipped,
                # selectivity) — what distinguishes a tenant's
                # filtered scan from a tiny file in /debug and fleet
                # rollups
                "pushdown": m.pushdown,
                # what the device decode plane did (backend jax/pallas):
                # launches by shape, bytes over the link, compiles (and
                # how much of them was lowering), the devices that held
                # the outputs, self seconds and entries by stage — a
                # client's only way to see that the chip answered and
                # where the request's time went; None on host reads
                "device": (m.device_stats.as_dict()
                           if m.device_stats.launches else None),
            }
        if req.want_trace and self.tracer is not None:
            # the client asked for the server-side spans: ship them with
            # the tracer's clock sample so the client can shift them
            # onto ITS perf_counter axis (Tracer.merge) and export one
            # cross-process Chrome trace. JSON turns span tuples into
            # lists; merge() unpacks either
            spans, clock = self.tracer.export_state()
            summary["trace"] = {"trace_id": self.tracer.trace_id,
                                "spans": spans, "clock": clock}
        return summary

    def _maybe_degrade(self, kwargs: dict) -> None:
        """Memory-pressure degrade step (utils.pressure): past the
        degrade watermark every newly-started scan runs with HALVED
        read-ahead (prefetched blocks are pure RSS) — the pipeline
        executor additionally shrinks its own in-flight chunk window.
        Slower, not failing; the shed watermark above this one is where
        admission starts refusing work."""
        from ..utils.pressure import LEVEL_DEGRADED, current_level

        if current_level() < LEVEL_DEGRADED:
            return
        self.degraded = True
        try:
            prefetch = int(str(kwargs.get("prefetch_blocks", 2)))
        except ValueError:
            prefetch = 2
        kwargs["prefetch_blocks"] = str(prefetch // 2)
