"""The long-lived multi-tenant scan server.

`ScanServer` is the deployable surface ROADMAP item 3 asks for: a
threaded TCP front-end speaking the frame protocol (serve/protocol.py),
an admission controller with per-tenant quotas and weighted fair
queueing (serve/admission.py), the streaming scan session
(serve/session.py), and an HTTP sidecar for `/metrics` + `/healthz`
(serve/http.py). Every scan in the process shares the process-wide
planes: ONE block cache + sparse-index store per `cache_dir`
(io.blockcache.shared_block_cache), ONE copybook/field-plan/code-page
compile cache (plan/cache.py), ONE metrics registry — so tenant B's
warm scan reuses tenant A's cached blocks and compiled plans.

Horizontal scale is N of these processes sharing one `cache_dir`
behind any TCP balancer (the caches are cross-process safe —
examples/serving_app.py is the recipe).
"""
from __future__ import annotations

import os
import socketserver
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

from ..obs.audit import (
    AuditLog,
    FlightRecorder,
    ScanRecord,
    record_from_summary,
)
from ..obs.metrics import serve_metrics, update_process_metrics
from ..obs.slo import Slo, SloTracker, parse_slos
from ..obs.trace import Tracer
from .admission import AdmissionController, AdmissionRejected, TenantQuota
from .http import ObsHttpServer
from .protocol import (
    FRAME_ERROR,
    FRAME_FINAL,
    FRAME_PROGRESS,
    FRAME_REQUEST,
    FRAME_TOKEN,
    ClientGone,
    FrameWriter,
    ProtocolError,
    ServeError,
    error_payload,
    parse_json,
    read_frame,
)
from .session import ScanRequest, ScanSession

# a connected peer must send its request frame within this window; a
# half-open socket must not pin a handler thread
REQUEST_READ_TIMEOUT_S = 30.0


class _ArrowFrameSink:
    """File-like sink pyarrow's IPC writer writes into; bytes forward
    to the connection as 'D' frames. Buffered per write_table call:
    the writer emits many small writes (message headers, buffers) per
    batch — one flush turns them into one-ish wire frame."""

    def __init__(self, writer: FrameWriter, metrics: dict, tenant: str):
        self._writer = writer
        self._metrics = metrics
        self._tenant = tenant
        self._buf: list = []
        self.closed = False

    def write(self, data) -> int:
        self._buf.append(bytes(data))
        return len(data)

    def flush_frames(self) -> None:
        if not self._buf:
            return
        payload = b"".join(self._buf)
        self._buf.clear()
        self._writer.data(payload)
        self._metrics["streamed_bytes"].labels(
            tenant=self._tenant).inc(len(payload))

    def flush(self) -> None:  # pyarrow may call it; framing is explicit
        pass

    def close(self) -> None:
        self.closed = True


class _StreamingTableWriter:
    """Lazily-opened Arrow IPC stream over the frame sink: the schema
    message goes out with the first table, each table becomes one or
    more record batches (`stream_batch_rows` caps rows per batch), and
    `close()` writes the IPC end-of-stream marker."""

    def __init__(self, sink: _ArrowFrameSink, metrics: dict, tenant: str,
                 max_chunksize: Optional[int]):
        self._sink = sink
        self._metrics = metrics
        self._tenant = tenant
        self._max_chunksize = max_chunksize or None
        self._writer = None
        self.first_batch_t: Optional[float] = None

    def write_table(self, table) -> None:
        import pyarrow as pa

        if self._writer is None:
            self._writer = pa.ipc.new_stream(self._sink, table.schema)
        if self.first_batch_t is None:
            self.first_batch_t = time.monotonic()
        self._writer.write_table(table, max_chunksize=self._max_chunksize)
        # arithmetic, not a second to_batches() pass over the table the
        # writer just chunked
        n = (1 if not self._max_chunksize
             else max(1, -(-table.num_rows // self._max_chunksize)))
        self._metrics["streamed_batches"].labels(
            tenant=self._tenant).inc(n)
        self._sink.flush_frames()

    def close(self, fallback_schema=None) -> None:
        """End the IPC stream; a scan that emitted nothing still sends
        a valid empty stream when the schema is known."""
        import pyarrow as pa

        if self._writer is None:
            if fallback_schema is None:
                return
            self._writer = pa.ipc.new_stream(self._sink, fallback_schema)
        self._writer.close()
        self._sink.flush_frames()


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        server: "ScanServer" = self.server  # type: ignore[assignment]
        writer = FrameWriter(self.wfile)
        tenant = "unknown"
        try:
            self.connection.settimeout(REQUEST_READ_TIMEOUT_S)
            ftype, payload = read_frame(self.rfile)
            if ftype != FRAME_REQUEST:
                raise ProtocolError(
                    f"expected a request frame, got {ftype!r}")
            doc = parse_json(payload)
            if "peer_block" in doc:
                # the peer block-cache tier (io/peercache.py): another
                # replica asking for one framed cache entry. Served
                # outside admission — a bounded disk read, no scan
                server._serve_peer_block(writer, doc["peer_block"])
                return
            request = ScanRequest(doc)
            tenant = request.tenant
            # the scan may legitimately run long between frames, but no
            # single SEND may block unboundedly: a connected peer that
            # stops reading would otherwise wedge this handler in a TCP
            # write forever — admission slot, byte gate, and assembly
            # thread all pinned. A stalled send times out (an OSError),
            # becomes ClientGone, and cancels the scan.
            self.connection.settimeout(server.send_timeout_s or None)
        except Exception as exc:
            writer.try_json(FRAME_ERROR, error_payload(exc, "protocol"))
            return
        # request-scoped tracer: built when the client asked for the
        # merged trace OR the flight recorder needs evidence; carries
        # the request's identity so every span groups under one trace_id
        tracer = server.request_tracer(request)
        t_req = time.monotonic()
        p_req = time.perf_counter()
        if server.draining:
            writer.try_json(FRAME_ERROR, {
                "error": "AdmissionRejected: server is draining",
                "code": "rejected", "reason": "draining",
                "tenant": tenant})
            server.record_rejection(request, "draining",
                                    "server is draining")
            return
        try:
            ticket = server.controller.admit(
                tenant, follower=request.is_follow)
        except AdmissionRejected as exc:
            writer.try_json(FRAME_ERROR, {
                "error": f"AdmissionRejected: {exc}",
                "code": "rejected", "reason": exc.reason,
                "tenant": exc.tenant})
            server.record_rejection(request, exc.reason, str(exc))
            return
        t_admit = time.monotonic()
        queue_wait_s = t_admit - t_req
        if tracer is not None:
            tracer.record_span(
                "queue_wait", "serve", p_req, time.perf_counter(),
                args={"tenant": tenant,
                      "request_id": request.request_id})
        m = server.metrics
        sink = _ArrowFrameSink(writer, m, tenant)
        table_writer = _StreamingTableWriter(
            sink, m, tenant,
            server.stream_batch_rows(request))
        outcome = "error"
        error_text = ""
        summary: dict = {}
        first_batch_s = None
        entry = server.register_active(request)
        # the admission slot releases the moment the scan's work is
        # done — BEFORE the final frame reaches the client — so a
        # serialized client (finish scan N, immediately send N+1) can
        # never observe its own completed scan still holding the slot
        # and be bounced with a spurious queue_full. Idempotent: the
        # error paths release from the handler's finally instead.
        released = [False]

        def release_slot() -> None:
            if not released[0]:
                released[0] = True
                server.controller.release(ticket)

        def on_progress(p):
            entry["progress"] = p.as_dict()  # the /debug/scans source
            if request.want_progress:
                writer.try_json(FRAME_PROGRESS, p.as_dict())

        on_plan = lambda fp: writer.try_json(  # noqa: E731
            # the stream's FIRST frame is a resume token carrying the
            # chunk-plan fingerprint: a client that dies at any later
            # point holds the plan identity it must resume against
            FRAME_TOKEN,
            {"plan": fp, "records": request.resume_records})
        if request.is_follow:
            from .follow import FollowSession

            session = FollowSession(
                request, server_options=server.server_options,
                controller=server.controller,
                on_progress=on_progress, tracer=tracer,
                force_progress=True, on_plan=on_plan,
                # the idle-gap liveness probe: a keepalive token whose
                # write failure IS the disconnect signal for a
                # subscriber waiting on a quiet source
                keepalive=lambda: writer.json(
                    FRAME_TOKEN, session.resume_token()))
            m["follow"].labels(tenant=tenant).inc()
        else:
            session = ScanSession(
                request, server_options=server.server_options,
                controller=server.controller,
                on_progress=on_progress, tracer=tracer,
                force_progress=True,
                force_field_costs=server.wants_field_costs(),
                on_plan=on_plan)
        if request.is_resume:
            m["resumed"].labels(tenant=tenant).inc()
        # resume tokens ride between data frames: after a table is on
        # the wire, the delivery watermark advanced — tell the client
        # (throttled), so a connection lost mid-stream resumes from the
        # last token instead of record 0. FrameWriter's lock keeps the
        # token frame-aligned between IPC fragments.
        token_last = [0.0]

        def write_table(table) -> None:
            table_writer.write_table(table)
            now = time.monotonic()
            if now - token_last[0] >= server.token_interval_s:
                token_last[0] = now
                writer.try_json(FRAME_TOKEN, session.resume_token())

        try:
            summary = session.run(write_table)
            table_writer.close(fallback_schema=session.result_schema)
            summary["bytes"] = writer.bytes_written
            summary["queue_wait_s"] = round(queue_wait_s, 6)
            if table_writer.first_batch_t is not None:
                first_batch_s = table_writer.first_batch_t - t_admit
                summary["first_batch_s"] = round(first_batch_s, 6)
                m["first_batch"].observe(first_batch_s)
            release_slot()
            writer.json(FRAME_FINAL, summary)
            outcome = "ok"
        except ClientGone:
            # peer went away mid-stream — the frame write raised inside
            # the batch callback and cancelled the scan; nothing left to
            # tell the client. (Only ClientGone means that: a scan can
            # itself die of an OSError — storage faults are IOErrors —
            # and those MUST still become an 'E' frame below.) Audited
            # as its own outcome: a client hanging up is not a server
            # failure, must not burn SLOs or spend flight-recorder dumps
            outcome = "client_gone"
            error_text = "ClientGone: peer disconnected mid-stream"
        except Exception as exc:
            # scan failure with the peer still connected: a structured
            # error frame, never a silent close (the pre-serve bridge
            # left clients blocked in a read here). A ServeError keeps
            # its own code (request hygiene failures are 'protocol')
            from ..streaming.sources import SourceTruncated

            if isinstance(exc, ServeError):
                code = exc.code
            elif isinstance(exc, SourceTruncated):
                # a followed source shrank below its watermark: the
                # structured outcome the chaos matrix pins — audited,
                # counted, never silently wrong rows
                code = "source_truncated"
            else:
                code = "scan_error"
            payload = error_payload(exc, code)
            if code == "scan_error" and session.plan_fp:
                # even a failed scan tells the client how far it got:
                # the failover attempt on another replica resumes from
                # here instead of re-streaming everything
                payload["resume_token"] = session.resume_token()
            # same slot discipline as the success path: the scan is
            # over — release BEFORE the error frame reaches the client,
            # so its immediate retry cannot bounce off the dead scan
            release_slot()
            writer.try_json(FRAME_ERROR, payload)
            error_text = f"{type(exc).__name__}: {exc}"
            if code == "protocol":
                # a request the server refused to run (reserved /
                # server-owned options): audited like an admission
                # rejection — a misbehaving CLIENT must not burn the
                # error-budget SLO or spend flight-recorder dumps
                outcome = "rejected"
        finally:
            release_slot()
            server.unregister_active(entry)
            # the Prometheus counter keeps its historical ok/error
            # vocabulary; the finer client_gone class lives on the
            # audit record
            m["completed"].labels(
                tenant=tenant,
                outcome="ok" if outcome == "ok" else "error").inc()
            if session.degraded:
                m["degraded"].labels(tenant=tenant).inc()
            server.observe_scan(
                request, summary, outcome=outcome, error=error_text,
                queue_wait_s=queue_wait_s, first_batch_s=first_batch_s,
                e2e_s=time.monotonic() - t_req, session=session,
                tracer=tracer)


class ScanServer(socketserver.ThreadingTCPServer):
    """Multi-tenant streaming scan service.

    Usage: ``srv = ScanServer(quotas={...}).start()`` ...
    ``srv.stop()``. `start()` runs the accept loop (and the HTTP obs
    sidecar) in daemon threads; `srv.address` is the scan endpoint,
    `srv.http_address` the `/metrics` + `/healthz` endpoint.

    `server_options` are read_cobol options forced onto every scan
    (e.g. ``{"cache_dir": "/var/cache/cobrix"}`` — the shared-plane
    pin); client options ride underneath them.

    Request-scoped observability (all off by default — a bare server
    adds zero per-record cost):

    * ``audit_log`` — JSONL path; one ScanRecord per completed /
      failed / rejected scan, size-rotated (``audit_max_mb`` /
      ``audit_keep``). `tools/scanlog.py` reads it.
    * ``slos`` — objective specs (strings like ``first_batch_p99=0.5``
      or `obs.slo.Slo` objects) evaluated per scan into Prometheus
      good/bad burn-rate counters and the `/healthz` + `/debug/slo`
      status.
    * ``flight_dir`` — evidence dumps (trace + field costs + record)
      for scans breaching a latency SLO or erroring; enabling it turns
      on span collection and field-cost attribution for every scan
      (in-memory only — no per-request artifacts on healthy scans).
    * the last ``flight_ring`` ScanRecords always sit in memory behind
      `/debug/recent` and `/debug/errors`.
    """

    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 default_quota: Optional[TenantQuota] = None,
                 quotas: Optional[Dict[str, TenantQuota]] = None,
                 max_concurrent_scans: int = 16,
                 queue_timeout_s: float = 30.0,
                 send_timeout_s: float = 120.0,
                 server_options: Optional[dict] = None,
                 http_host: Optional[str] = None, http_port: int = 0,
                 enable_http: bool = True,
                 audit_log: str = "",
                 audit_max_mb: float = 64.0,
                 audit_keep: int = 3,
                 slos: Optional[Sequence[Union[str, Slo]]] = None,
                 flight_dir: str = "",
                 flight_ring: int = 64,
                 flight_max_dumps: int = 200,
                 drain_timeout_s: float = 30.0,
                 token_interval_s: float = 1.0,
                 memory_budget_mb: float = 0.0,
                 degrade_fraction: float = 0.75,
                 shed_fraction: float = 0.9,
                 fleet: bool = False,
                 replica_id: str = "",
                 heartbeat_interval_s: float = 2.0,
                 fleet_scrape_timeout_s: float = 2.0,
                 queue_wait_target_s: float = 0.5,
                 fleet_dir: str = "",
                 peer_cache: bool = True,
                 peer_timeout_s: float = 2.0):
        if fleet and not (server_options or {}).get("cache_dir"):
            # checked before the listener binds: a config error must
            # not leak a bound socket
            raise ValueError(
                "fleet mode needs a shared cache_dir in "
                "server_options (the replica registry lives under "
                "<cache_dir>/fleet)")
        # every answer is Arrow IPC, so load pyarrow here, on the thread
        # that builds the server, and not on the first connection's
        # handler thread: pyarrow 25's allocator crashes later threads
        # once its first loader has exited (engine/pipeline._finalizers)
        import pyarrow  # noqa: F401

        super().__init__((host, port), _Handler)
        # max seconds ONE frame write may block on a non-reading peer
        # before the scan is cancelled as ClientGone (0 = unbounded)
        self.send_timeout_s = max(0.0, float(send_timeout_s))
        # min seconds between mid-stream resume-token ('T') frames
        self.token_interval_s = max(0.0, float(token_interval_s))
        # overload shedding: a positive budget installs the
        # process-wide memory watermark (utils.pressure) — past the
        # degrade fraction new scans run with shrunk io/pipeline knobs,
        # past the shed fraction admission refuses work with structured
        # `overloaded` rejections instead of riding into the OOM-killer.
        # The server owns what it installed: stop() uninstalls it so a
        # stopped server's budget cannot throttle unrelated in-process
        # work (embedders / tests constructing several servers)
        self._installed_budget = bool(memory_budget_mb
                                      and memory_budget_mb > 0)
        if self._installed_budget:
            from ..utils.pressure import set_process_budget

            set_process_budget(
                int(memory_budget_mb * 1024 * 1024),
                degrade_fraction=degrade_fraction,
                shed_fraction=shed_fraction)
        self.metrics = serve_metrics()
        self.controller = AdmissionController(
            default_quota=default_quota, quotas=quotas,
            max_concurrent_scans=max_concurrent_scans,
            queue_timeout_s=queue_timeout_s, metrics=self.metrics)
        self.server_options = dict(server_options or {})
        self.drain_timeout_s = max(0.0, float(drain_timeout_s))
        # -- request-scoped observability -------------------------------
        self.audit = (AuditLog(audit_log, max_mb=audit_max_mb,
                               keep=audit_keep) if audit_log else None)
        slo_objs: List[Slo] = []
        for s in (slos or ()):
            slo_objs.extend(parse_slos([s]) if isinstance(s, str)
                            else [s])
        self.slo = SloTracker(slo_objs) if slo_objs else None
        self.flight_dir = flight_dir
        self.flight = FlightRecorder(ring_size=flight_ring,
                                     dump_dir=flight_dir,
                                     max_dumps=flight_max_dumps)
        self._active_scans: Dict[int, dict] = {}
        self._active_seq = 0
        self._active_lock = threading.Lock()
        self.draining = False
        self._started_at = time.monotonic()
        self._started_wall = time.time()
        # -- fleet observability plane (opt-in; see cobrix_tpu.fleet) ---
        # built ONLY when asked: a non-fleet server never imports the
        # fleet package, never writes a heartbeat, never takes a
        # fingerprint-heat timestamp — the zero-overhead contract
        # tools/fleetcheck.py counter-asserts
        self._fleet = None
        self._heartbeater = None
        self._peer_cache_host = None  # the BlockCache holding our tier
        self.queue_wait_target_s = max(0.0, float(queue_wait_target_s))
        if fleet:
            cache_dir = str(self.server_options.get("cache_dir"))
            from ..fleet.federate import FleetFederator
            from ..fleet.registry import (FingerprintHeat, Heartbeater,
                                          ReplicaRegistry,
                                          default_replica_id)
            import socket as _socket

            self.replica_id = (str(replica_id) if replica_id
                               else default_replica_id())
            self._fleet = {
                # fleet_dir decouples the membership root from the
                # block-cache root: replicas on per-node disks keep
                # private cache_dirs but still share one registry (the
                # split the peer cache tier exists for). Default: the
                # shared-cache_dir layout PR 12 shipped.
                "registry": ReplicaRegistry(
                    fleet_dir or os.path.join(cache_dir, "fleet"),
                    interval_s=heartbeat_interval_s),
                "heat": FingerprintHeat(),
                "interval_s": max(0.05, float(heartbeat_interval_s)),
                "host": _socket.gethostname(),
            }
            self._fleet["federator"] = FleetFederator(
                self._fleet["registry"],
                timeout_s=fleet_scrape_timeout_s)
            self._heartbeater = Heartbeater(
                self._fleet["registry"], self._fleet_record,
                interval_s=self._fleet["interval_s"])
            if peer_cache:
                # attach the peer tier to the process's shared block
                # cache: every CachingSource under this cache_dir now
                # asks a warm peer before the storage backend
                from ..io.blockcache import shared_block_cache
                from ..io.peercache import (PeerCacheTier,
                                            registry_peers_fn)

                max_bytes = int(float(self.server_options.get(
                    "cache_max_mb", 1024.0)) * 1024 * 1024)
                host_cache = shared_block_cache(cache_dir, max_bytes)
                host_cache.peer_tier = PeerCacheTier(
                    registry_peers_fn(self._fleet["registry"],
                                      self.replica_id),
                    replica_id=self.replica_id,
                    timeout_s=peer_timeout_s)
                self._peer_cache_host = host_cache
        else:
            self.replica_id = str(replica_id) or ""
        self._http: Optional[ObsHttpServer] = None
        if enable_http:
            self._http = ObsHttpServer(
                snapshot_fn=self._health_snapshot,
                debug_fn=self._debug,
                pre_scrape=self._pre_scrape,
                fleet_fn=(self._fleet_endpoint if self._fleet is not None
                          else None),
                stats_fn=self._stats_snapshot,
                host=http_host if http_host is not None else host,
                port=http_port)
        self._thread: Optional[threading.Thread] = None

    # -- knobs ----------------------------------------------------------

    def stream_batch_rows(self, request: ScanRequest) -> Optional[int]:
        """Rows-per-record-batch cap for one request: the client's
        `stream_batch_rows` option (validated by parse_options during
        the scan), else the server default (None = per-chunk). Presence
        check, not truthiness — an explicit client 0 means 'one batch
        per chunk' and must not fall through to the server default."""
        raw = request.options.get("stream_batch_rows")
        if raw is None:
            raw = self.server_options.get("stream_batch_rows")
        try:
            n = int(str(raw)) if raw is not None else 0
        except ValueError:
            n = 0
        return n if n > 0 else None

    # -- request-scoped observability -----------------------------------

    def wants_field_costs(self) -> bool:
        """Flight-recorder evidence includes the per-field cost table,
        so a configured dump dir turns attribution on for every scan."""
        return bool(self.flight_dir)

    def request_tracer(self, request: ScanRequest) -> Optional[Tracer]:
        """A per-request Tracer when anyone will read its spans: the
        client asked for the merged trace, or a flight-recorder dump may
        need evidence. None otherwise — the zero-overhead default."""
        if not (request.want_trace or self.flight_dir):
            return None
        return Tracer(process_name="request",
                      trace_id=request.trace_id,
                      meta={"request_id": request.request_id,
                            "tenant": request.tenant})

    def register_active(self, request: ScanRequest) -> dict:
        """The `/debug/scans` live entry; the handler's progress
        callback mutates ``entry['progress']`` in place. Keyed by a
        server-local token, NOT the client-minted request_id — a client
        retrying with the same id while its first attempt still streams
        must not evict the live entry of either attempt."""
        entry = {
            "request_id": request.request_id,
            "trace_id": request.trace_id,
            "tenant": request.tenant,
            "files": list(request.files),
            "started_unix": round(time.time(), 3),
            "progress": None,
        }
        with self._active_lock:
            self._active_seq += 1
            key = self._active_seq
            self._active_scans[key] = entry
        entry["_key"] = key
        return entry

    def unregister_active(self, entry: dict) -> None:
        with self._active_lock:
            self._active_scans.pop(entry.get("_key"), None)

    def record_rejection(self, request: ScanRequest, reason: str,
                         detail: str) -> None:
        """Rejected scans get audit records too — 'why did my request
        vanish' must be answerable from the log alone."""
        record = ScanRecord(
            request_id=request.request_id, trace_id=request.trace_id,
            tenant=request.tenant, outcome="rejected", ts=time.time(),
            files=list(request.files), error=f"{reason}: {detail}",
            resume_of=((request.resume_of or "?")
                       if request.is_resume else ""))
        self._observe_record(record, tracer=None, field_costs=None)

    def observe_scan(self, request: ScanRequest, summary: dict,
                     outcome: str, error: str, queue_wait_s: float,
                     first_batch_s: Optional[float], e2e_s: float,
                     session: ScanSession,
                     tracer: Optional[Tracer]) -> None:
        """One completed/failed scan -> audit record -> SLO counters ->
        flight recorder. Never raises: observability of a scan must not
        fail the NEXT request on this connection pool."""
        try:
            record = record_from_summary(
                request.request_id, request.trace_id, request.tenant,
                request.files, summary, outcome=outcome, error=error,
                queue_wait_s=round(queue_wait_s, 6),
                first_batch_s=(round(first_batch_s, 6)
                               if first_batch_s is not None else None),
                e2e_s=round(e2e_s, 6),
                # only an HONORED resume (it actually skipped records)
                # ties and SLO-exempts — a zero-record resume shape is
                # an ordinary scan and must account like one
                resume_of=((request.resume_of or "?")
                           if request.is_resume else ""))
            field_costs = (session.metrics.field_costs
                           if session.metrics is not None else None)
            self._observe_record(record, tracer=tracer,
                                 field_costs=field_costs)
            if self._fleet is not None:
                self._note_fleet_heat(request, session.plan_fp)
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "failed to record scan observability for request %s",
                request.request_id, exc_info=True)

    def _observe_record(self, record: ScanRecord, tracer,
                        field_costs) -> None:
        if self.slo is not None:
            # stamps record.slo_breaches; on an 'ok' scan only latency/
            # throughput objectives can breach (error_rate passes), so
            # a breach list on a good scan IS the dump trigger set
            self.slo.observe(record)
        self.flight.observe(record, tracer=tracer,
                            field_costs=field_costs)
        if self.audit is not None:
            self.audit.append(record)

    # -- peer block serving ----------------------------------------------

    def _serve_peer_block(self, writer: FrameWriter, spec) -> None:
        """Answer one peer_block request: the raw framed cache entry as
        'D' frame(s) + 'F' {found}. Read-only and unauthenticated like
        the rest of the scan plane; a miss (absent entry, no cache_dir,
        torn tail) is a structured {found: false}, never an error. Any
        server with a cache_dir answers — the REQUESTING side is what
        fleet mode gates."""
        try:
            url = str(spec["url"])
            fingerprint = str(spec["fingerprint"])
            start, end = int(spec["start"]), int(spec["end"])
            if not (0 <= start < end):
                raise ValueError(f"bad range [{start}, {end})")
            from ..io.peercache import MAX_PEER_BLOCK_BYTES

            if end - start > MAX_PEER_BLOCK_BYTES:
                raise ValueError("peer_block range too large")
        except (KeyError, TypeError, ValueError) as exc:
            writer.try_json(FRAME_ERROR, error_payload(exc, "protocol"))
            return
        entry = None
        cache_dir = self.server_options.get("cache_dir")
        if cache_dir:
            from ..io.blockcache import raw_block_entry

            entry = raw_block_entry(str(cache_dir), url, fingerprint,
                                    start, end)
        try:
            if entry is None:
                self.metrics["peer_served"].labels(result="miss").inc()
                writer.json(FRAME_FINAL, {"found": False})
            else:
                self.metrics["peer_served"].labels(result="hit").inc()
                writer.data(entry)
                writer.json(FRAME_FINAL,
                            {"found": True, "bytes": len(entry)})
        except ClientGone:
            pass  # the asking peer gave up (its timeout): nothing owed

    # -- fleet plane -----------------------------------------------------

    def _fleet_record(self):
        """One heartbeat payload: the live admission/pressure snapshot
        plus cache hit totals and fingerprint heat. Built per beat on
        the heartbeat thread — never on a scan path."""
        from ..fleet.registry import ReplicaRecord
        from ..obs.metrics import scan_metrics, stream_metrics

        snap = self.controller.snapshot()
        cache: Dict[str, int] = {}
        for key, value in scan_metrics()["io_cache"].items().items():
            labels = dict(key)
            name = f"{labels.get('plane', '?')}_{labels.get('result', '?')}"
            cache[name] = cache.get(name, 0) + int(value)
        stream = stream_metrics()
        followers = sum(t.get("followers", 0)
                        for t in snap.get("tenants", {}).values())
        return ReplicaRecord(
            replica_id=self.replica_id,
            pid=os.getpid(),
            host=self._fleet["host"],
            scan_address=list(self.address),
            http_address=(list(self.http_address)
                          if self.http_address else None),
            started_at=self._started_wall,
            heartbeat_at=time.time(),
            interval_s=self._fleet["interval_s"],
            seq=self._next_heartbeat_seq(),
            draining=self.draining,
            pressure=(snap.get("pressure") or {}).get("level", "ok"),
            active_scans=int(snap.get("active_scans") or 0),
            queued_scans=int(snap.get("queued_scans") or 0),
            followers=int(followers),
            max_concurrent_scans=self.controller.max_concurrent_scans,
            lag_bytes=int(stream["lag_bytes"].value()),
            watermark_age_s=float(stream["watermark_age"].value()),
            cache=cache,
            heat=self._fleet["heat"].top(8))

    def _next_heartbeat_seq(self) -> int:
        self._fleet["seq"] = self._fleet.get("seq", 0) + 1
        return self._fleet["seq"]

    def _note_fleet_heat(self, request: ScanRequest,
                         plan_fp: str) -> None:
        """One heat bump per scan (fleet mode only): the plan
        fingerprint plus each input path — the affinity currency the
        routing front of ROADMAP item 5 will key on."""
        keys = [f"file:{f}" for f in request.files]
        if plan_fp:
            keys.append(f"plan:{plan_fp}")
        self._fleet["heat"].bump(keys)

    def _fleet_endpoint(self, path: str, query: dict):
        """`/fleet/<path>` documents (None -> 404). `replicas`, `slo`,
        `signals`, and `stats` are JSON; `metrics` is a federated
        Prometheus exposition. A federation refusal (bucket mismatch)
        propagates and the sidecar answers a structured 500."""
        fed = self._fleet["federator"]
        if path == "replicas":
            return fed.view().replicas_doc()
        if path == "metrics":
            return (fed.cluster_exposition(),
                    "text/plain; version=0.0.4; charset=utf-8")
        if path == "slo":
            return fed.slo_rollup()
        if path == "signals":
            from ..fleet.signals import derive_signals

            view = fed.view()
            return derive_signals(
                view, history=fed.history(),
                slo_rollup=fed.slo_rollup(view),
                queue_wait_target_s=self.queue_wait_target_s)
        if path == "stats":
            # federate the per-replica data-statistics snapshots: one
            # document per registry replica (unreachable ones degrade
            # to an error entry, never a failed endpoint)
            import json as _json

            from ..fleet.federate import _http_get

            per_replica = {}
            for scrape in fed.view().replicas:
                addr = scrape.status.record.http_address
                rid = scrape.replica_id
                if rid == self.replica_id:
                    per_replica[rid] = self._stats_snapshot()
                    continue
                if scrape.status.state != "live" or not addr:
                    per_replica[rid] = {"error": scrape.status.state}
                    continue
                try:
                    per_replica[rid] = _json.loads(_http_get(
                        f"http://{addr[0]}:{int(addr[1])}/stats",
                        timeout_s=2.0))
                except Exception as exc:
                    per_replica[rid] = {
                        "error": f"{type(exc).__name__}: {exc}"}
            return {"replicas": per_replica}
        return None

    # -- health + /debug -------------------------------------------------

    def _stats_snapshot(self) -> dict:
        """The `/stats` document: profile summaries this process built
        or loaded plus the recent ingest-drift ring (stats/service.py —
        imported lazily so a server that never touches statistics never
        imports the stats package)."""
        from ..stats import service

        return service.snapshot()

    def _health_snapshot(self) -> dict:
        doc: dict = {}
        if self.draining:
            doc["status"] = "draining"
        if self._fleet is not None:
            doc["replica_id"] = self.replica_id
        doc.update(self.controller.snapshot())
        if self.slo is not None:
            doc["slo"] = self.slo.status()
        return doc

    def _pre_scrape(self) -> None:
        with self._active_lock:
            open_scans = len(self._active_scans)
        update_process_metrics(open_scans=open_scans)

    def _debug(self, path: str, query: dict) -> Optional[object]:
        """`/debug/<path>` documents (None -> 404)."""
        if path == "scans":
            with self._active_lock:
                return {"scans": [
                    {k: v for k, v in e.items()
                     if not k.startswith("_")}
                    for e in self._active_scans.values()]}
        if path in ("recent", "errors"):
            try:
                n = int(query.get("n", "50"))
            except ValueError:
                n = 50
            records = self.flight.recent(
                n=n, outcome=("bad" if path == "errors" else None))
            return {path: [r.as_dict() for r in records]}
        if path == "slo":
            return {"slo": self.slo.status() if self.slo else {},
                    "configured": self.slo is not None}
        if path == "config":
            return {
                "address": list(self.address),
                "draining": self.draining,
                "max_concurrent_scans":
                    self.controller.max_concurrent_scans,
                "queue_timeout_s": self.controller.queue_timeout_s,
                "send_timeout_s": self.send_timeout_s,
                "drain_timeout_s": self.drain_timeout_s,
                "server_options": dict(self.server_options),
                "default_quota": vars(self.controller.default_quota),
                "quotas": {t: vars(q) for t, q in
                           self.controller.quotas.items()},
                "audit_log": self.audit.path if self.audit else "",
                "flight_dir": self.flight_dir,
                "slos": [vars(s) for s in
                         (self.slo.slos if self.slo else [])],
            }
        return None

    # -- lifecycle ------------------------------------------------------

    @property
    def address(self) -> Tuple[str, int]:
        return self.server_address

    @property
    def http_address(self) -> Optional[Tuple[str, int]]:
        return self._http.address if self._http is not None else None

    def start(self) -> "ScanServer":
        if self._http is not None:
            self._http.start()
        if self._heartbeater is not None:
            # first beat synchronously: the replica is a fleet member
            # the moment start() returns, not one interval later
            self._heartbeater._beat()
            self._heartbeater.start()
        self._thread = threading.Thread(target=self.serve_forever,
                                        name="cobrix-serve-accept",
                                        daemon=True)
        self._thread.start()
        return self

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful shutdown, balancer-style: stop accepting scans
        (new requests get a structured ``draining`` rejection, and
        `/healthz` answers 503 so balancers stop routing), let in-flight
        scans finish for up to `timeout_s` (default `drain_timeout_s`),
        then flush the audit log. Returns True when every scan finished
        inside the window — False means scans were abandoned and the
        process should exit nonzero. The HTTP sidecar stays up
        throughout (a draining process must still answer health
        checks); `stop()` tears it down afterwards."""
        self.draining = True
        if self._thread is not None:  # stop the accept loop first
            self.shutdown()
            self._thread.join(timeout=5)
            self._thread = None
        self.server_close()
        window = (self.drain_timeout_s if timeout_s is None
                  else max(0.0, float(timeout_s)))
        deadline = time.monotonic() + window
        clean = False
        while True:
            snap = self.controller.snapshot()
            if snap["active_scans"] == 0 and snap["queued_scans"] == 0:
                clean = True
                break
            if time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        if self.audit is not None:
            self.audit.flush()
        return clean

    def stop(self) -> None:
        if self._thread is not None:  # shutdown() deadlocks when
            self.shutdown()           # serve_forever never ran
            self._thread.join(timeout=5)
            self._thread = None
        self.server_close()
        if self._heartbeater is not None:
            # clean exit unregisters: the fleet view drops this replica
            # immediately instead of after heartbeat expiry
            self._heartbeater.stop(unregister=True)
            self._heartbeater = None
        if self._peer_cache_host is not None:
            # the server owns the tier it attached: a stopped server's
            # peers must not be consulted by unrelated in-process reads
            self._peer_cache_host.peer_tier = None
            self._peer_cache_host = None
        if self._http is not None:
            self._http.stop()
        if getattr(self, "_installed_budget", False):
            from ..utils.pressure import set_process_budget

            set_process_budget(0)
            self._installed_budget = False


def main(argv=None) -> int:
    """``python -m cobrix_tpu.serve [--host H] [--port P] [--http-port P]
    [--cache-dir DIR] [--max-concurrent N] [--audit-log PATH]
    [--slo SPEC ...] [--flight-dir DIR] [--drain-timeout S]
    [--fleet [--replica-id ID] [--heartbeat-interval S]
    [--queue-wait-target S]]``

    SIGTERM/SIGINT start a graceful drain: the listener closes,
    `/healthz` answers 503 ``draining``, in-flight scans get
    ``--drain-timeout`` seconds to finish, the audit log is flushed,
    and the process exits 0 (clean) or 1 (scans were aborted)."""
    import argparse
    import signal

    ap = argparse.ArgumentParser(
        description="cobrix_tpu multi-tenant streaming scan server")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8816)
    ap.add_argument("--http-port", type=int, default=8817)
    ap.add_argument("--cache-dir", default="",
                    help="shared block/index cache root (pins every "
                         "scan to one warm plane)")
    ap.add_argument("--max-concurrent", type=int, default=16)
    ap.add_argument("--tenant-concurrent", type=int, default=4,
                    help="default per-tenant concurrent-scan quota")
    ap.add_argument("--audit-log", default="",
                    help="JSONL scan audit log path (rotated; "
                         "tools/scanlog.py reads it)")
    ap.add_argument("--audit-max-mb", type=float, default=64.0)
    ap.add_argument("--slo", action="append", default=[],
                    metavar="SPEC",
                    help="objective, e.g. first_batch_p99=0.5, "
                         "e2e_p95=3.0, roofline_min=0.05, "
                         "error_rate=0.01 (repeatable)")
    ap.add_argument("--flight-dir", default="",
                    help="flight-recorder dump dir for scans breaching "
                         "a latency SLO or erroring")
    ap.add_argument("--flight-max-dumps", type=int, default=200,
                    help="lifetime cap on evidence dumps (disk-fill "
                         "guard; exhaustion is logged once)")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="seconds in-flight scans get to finish on "
                         "SIGTERM/SIGINT before forced abort")
    ap.add_argument("--memory-budget-mb", type=float, default=0.0,
                    help="process RSS budget: past 75%% new scans run "
                         "degraded (halved read-ahead, shrunk chunk "
                         "window), past 90%% admission sheds with "
                         "structured 'overloaded' rejections "
                         "(0 = no watermark)")
    ap.add_argument("--fleet", action="store_true",
                    help="join the fleet observability plane: heartbeat "
                         "into <cache-dir>/fleet and serve "
                         "/fleet/{replicas,metrics,slo,signals} "
                         "(requires --cache-dir)")
    ap.add_argument("--fleet-dir", default="",
                    help="membership root override (default "
                         "<cache-dir>/fleet): replicas with PRIVATE "
                         "per-node cache dirs share one registry here, "
                         "and the peer block-cache tier fills the gap")
    ap.add_argument("--no-peer-cache", action="store_true",
                    help="fleet mode: do not consult warm peers on "
                         "local block-cache misses")
    ap.add_argument("--peer-timeout", type=float, default=2.0,
                    help="wall-clock budget for one peer block fetch "
                         "before degrading to the storage backend")
    ap.add_argument("--route", action="store_true",
                    help="run the fleet ROUTING FRONT instead of a scan "
                         "server: consistent-hash + health-aware proxy "
                         "over the registry's live replicas "
                         "(requires --cache-dir or --fleet-dir)")
    ap.add_argument("--replica-id", default="",
                    help="fleet replica identity (default: "
                         "hostname-pid)")
    ap.add_argument("--heartbeat-interval", type=float, default=2.0,
                    help="seconds between fleet heartbeats; a killed "
                         "replica leaves the live view within about "
                         "1.6 intervals")
    ap.add_argument("--queue-wait-target", type=float, default=0.5,
                    help="fleet autoscaling signal: queue-wait p90 over "
                         "this many seconds recommends scale-up")
    args = ap.parse_args(argv)
    if args.route:
        if not (args.cache_dir or args.fleet_dir):
            ap.error("--route requires --cache-dir or --fleet-dir "
                     "(the routing front reads the replica registry)")
        from ..fleet.router import run_route_server

        return run_route_server(
            host=args.host, port=args.port,
            fleet_dir=(args.fleet_dir
                       or os.path.join(args.cache_dir, "fleet")),
            heartbeat_interval_s=args.heartbeat_interval)
    if args.fleet and not args.cache_dir:
        ap.error("--fleet requires --cache-dir (the replica registry "
                 "lives in the shared cache root)")
    server_options = ({"cache_dir": args.cache_dir} if args.cache_dir
                      else None)
    srv = ScanServer(
        args.host, args.port,
        default_quota=TenantQuota(max_concurrent=args.tenant_concurrent),
        max_concurrent_scans=args.max_concurrent,
        server_options=server_options,
        http_port=args.http_port,
        audit_log=args.audit_log, audit_max_mb=args.audit_max_mb,
        slos=args.slo, flight_dir=args.flight_dir,
        flight_max_dumps=args.flight_max_dumps,
        drain_timeout_s=args.drain_timeout,
        memory_budget_mb=args.memory_budget_mb,
        fleet=args.fleet, replica_id=args.replica_id,
        heartbeat_interval_s=args.heartbeat_interval,
        queue_wait_target_s=args.queue_wait_target,
        fleet_dir=args.fleet_dir,
        peer_cache=not args.no_peer_cache,
        peer_timeout_s=args.peer_timeout)
    print(f"cobrix_tpu serving scans on {srv.address}, "
          f"obs on {srv.http_address}", flush=True)
    stop_signal = threading.Event()

    def _on_signal(signum, frame):
        stop_signal.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    srv.start()
    stop_signal.wait()
    print("cobrix_tpu serve: draining "
          f"(up to {args.drain_timeout:.0f}s)...", flush=True)
    clean = srv.drain()
    srv.stop()
    print("cobrix_tpu serve: "
          + ("drained clean" if clean else
             "FORCED abort: in-flight scans abandoned"), flush=True)
    return 0 if clean else 1


if __name__ == "__main__":
    raise SystemExit(main())
