"""Serving tier tests (cobrix_tpu.serve): the multi-tenant streaming
scan server end to end through real sockets.

The matrix: streamed ≡ one-shot parity (rows/schema/diagnostics
metadata) for fixed and variable-length inputs; concurrent multi-tenant
scans with quota rejection and tenant isolation; mid-stream server-side
faults (ChaosSource) surfacing as structured client errors — never a
hang; warm-cache re-scans proving the shared block/index planes from
the client-visible trailer; `/metrics` + `/healthz` scrape format; live
progress frames over the wire; and the bridge shim's client-side
timeouts. Everything sits under `hard_timeout` so a protocol bug fails
loud instead of wedging CI.
"""
import json
import os
import tempfile
import threading
import time
import urllib.error
import urllib.request
import uuid

import pytest

from cobrix_tpu import read_cobol
from cobrix_tpu.bridge import BridgeServer, read_remote
from cobrix_tpu.obs.progress import ScanProgress
from cobrix_tpu.reader.stream import RetryPolicy
from cobrix_tpu.serve import (
    AdmissionController,
    AdmissionRejected,
    ScanServer,
    ServeError,
    TenantQuota,
    fetch_table,
    flight_available,
    stream_scan,
)
from cobrix_tpu.testing.faults import register_chaos_backend
from cobrix_tpu.testing.generators import (
    EXP1_COPYBOOK,
    EXP2_COPYBOOK,
    generate_exp1,
    generate_exp2,
)

from util import hard_timeout

# multi-chunk on purpose: ~3 MB of fixed records against a 1 MB chunk
# size, so streaming yields many batches and first-batch latency is a
# real fraction of the scan
FIXED_RECORDS = 20_000
FIXED_OPTS = dict(copybook_contents=EXP1_COPYBOOK, chunk_size_mb="1",
                  pipeline_workers="2")

EXP2_OPTS = dict(copybook_contents=EXP2_COPYBOOK, is_record_sequence="true",
                 segment_field="SEGMENT-ID",
                 redefine_segment_id_map="STATIC-DETAILS => C",
                 **{"redefine_segment_id_map:1": "CONTACTS => P"})


@pytest.fixture(scope="module")
def fixed_file():
    path = tempfile.mktemp(suffix=".dat")
    with open(path, "wb") as f:
        f.write(generate_exp1(FIXED_RECORDS, seed=5).tobytes())
    yield path
    os.unlink(path)


@pytest.fixture(scope="module")
def vrl_file():
    path = tempfile.mktemp(suffix=".dat")
    with open(path, "wb") as f:
        f.write(generate_exp2(600, seed=11))
    yield path
    os.unlink(path)


@pytest.fixture()
def server():
    srv = ScanServer().start()
    yield srv
    srv.stop()


def http_get(srv, path):
    host, port = srv.http_address
    try:
        with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                    timeout=10) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as err:  # non-2xx still has a body
        return err.code, dict(err.headers), err.read()


# -- streamed ≡ one-shot parity ------------------------------------------


def test_streamed_matches_one_shot_fixed(server, fixed_file):
    with hard_timeout(180, "fixed stream parity"):
        # iterating surface: incremental batches, client memory O(batch)
        # (batches are NOT retained by the stream — collect our own)
        batches = []
        with stream_scan(server.address, fixed_file,
                         **FIXED_OPTS) as stream:
            for batch in stream:
                batches.append(batch)
            summary = stream.summary
            assert stream._batches == []  # iterate-only keeps nothing
            with pytest.raises(RuntimeError, match="already partially"):
                stream.table()  # iterate OR collect, never both
        local = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
        assert len(batches) > 1  # incremental, not one blob
        assert sum(b.num_rows for b in batches) == local.num_rows
        # collecting surface: table() drives a fresh stream
        with stream_scan(server.address, fixed_file,
                         **FIXED_OPTS) as stream:
            remote = stream.table()
        assert remote.schema == local.schema  # includes field metadata
        assert remote.schema.metadata == local.schema.metadata
        assert remote.equals(local)
        assert summary["rows"] == local.num_rows
        assert summary["bytes"] > 0


def test_streamed_matches_one_shot_var_len(server, vrl_file):
    with hard_timeout(180, "VRL stream parity"):
        opts = dict(EXP2_OPTS, pipeline_workers="2")
        remote = fetch_table(server.address, vrl_file, **opts)
        local = read_cobol(vrl_file, **opts).to_arrow()
        assert remote.schema == local.schema
        assert remote.schema.metadata == local.schema.metadata
        assert remote.to_pylist() == local.to_pylist()


def test_streamed_diagnostics_metadata_round_trips(server, vrl_file):
    """A scan that ledgers errors ships the ReadDiagnostics JSON in the
    trailer, and the assembled table carries it byte-identically."""
    with hard_timeout(180, "diagnostics parity"):
        # corrupt a copy mid-file so permissive mode ledgers records
        raw = bytearray(open(vrl_file, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        path = tempfile.mktemp(suffix=".dat")
        with open(path, "wb") as f:
            f.write(raw)
        try:
            opts = dict(EXP2_OPTS, record_error_policy="permissive")
            remote = fetch_table(server.address, path, **opts)
            local = read_cobol(path, **opts).to_arrow()
            key = b"cobrix_tpu.read_diagnostics"
            assert remote.schema.metadata.get(key) \
                == local.schema.metadata.get(key)
        finally:
            os.unlink(path)


def test_max_records_caps_stream(server, fixed_file):
    with hard_timeout(120, "max_records"):
        t = fetch_table(server.address, fixed_file, max_records=7,
                        **FIXED_OPTS)
        assert t.num_rows == 7


def test_empty_result_is_a_valid_stream(server, fixed_file):
    with hard_timeout(120, "empty stream"):
        t = fetch_table(server.address, fixed_file, max_records=0,
                        **FIXED_OPTS)
        assert t.num_rows == 0
        assert len(t.schema) > 0  # schema still travels


# -- multi-tenant admission ----------------------------------------------


def test_quota_rejection_keeps_other_tenants_running(fixed_file):
    """Two tenants with quota 1 each: tenant A's second concurrent scan
    is REJECTED with a structured error while tenant B's scan still
    completes; stopping the server leaks no threads."""
    baseline = threading.active_count()
    srv = ScanServer(
        default_quota=TenantQuota(max_concurrent=1, max_queued=0)).start()
    try:
        with hard_timeout(180, "quota rejection"):
            first_batch = threading.Event()
            outcome = {}

            def tenant_a_scan():
                with stream_scan(srv.address, fixed_file, tenant="a",
                                 **FIXED_OPTS) as s:
                    it = iter(s)
                    next(it)
                    first_batch.set()
                    time.sleep(0.8)  # hold the quota slot
                    for _ in it:
                        pass
                    outcome["a1"] = s.summary["rows"]

            holder = threading.Thread(target=tenant_a_scan)
            holder.start()
            assert first_batch.wait(60)
            with pytest.raises(ServeError) as err:
                fetch_table(srv.address, fixed_file, tenant="a",
                            **FIXED_OPTS)
            assert err.value.code == "rejected"
            assert "retry" in str(err.value)
            # tenant B is untouched by A's quota exhaustion
            t = fetch_table(srv.address, fixed_file, tenant="b",
                            **FIXED_OPTS)
            assert t.num_rows == FIXED_RECORDS
            holder.join()
            assert outcome["a1"] == FIXED_RECORDS
    finally:
        srv.stop()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        leftover = [t.name for t in threading.enumerate()
                    if t.name.startswith("cobrix-serve")]
        if not leftover and threading.active_count() <= baseline:
            break
        time.sleep(0.05)
    assert not leftover
    assert threading.active_count() <= baseline


def test_admission_weighted_fair_share_drains_heavier_tenant_faster():
    """Unit-level: with weight 2 vs 1 and one global slot, the heavy
    tenant's queue drains about twice as fast — its last grant lands
    before the light tenant's."""
    with hard_timeout(60, "fair share"):
        ctl = AdmissionController(
            quotas={"heavy": TenantQuota(weight=2.0, max_queued=16),
                    "light": TenantQuota(weight=1.0, max_queued=16)},
            max_concurrent_scans=1, queue_timeout_s=30.0)
        hold = ctl.admit("light")
        order = []
        lock = threading.Lock()

        def waiter(tenant):
            ticket = ctl.admit(tenant)
            with lock:
                order.append(tenant)
            ctl.release(ticket)

        threads = []
        for i in range(4):
            for tenant in ("heavy", "light"):
                t = threading.Thread(target=waiter, args=(tenant,))
                t.start()
                threads.append(t)
        time.sleep(0.3)  # everyone queued behind the held slot
        ctl.release(hold)
        for t in threads:
            t.join(30)
        assert len(order) == 8
        last_heavy = max(i for i, t in enumerate(order) if t == "heavy")
        last_light = max(i for i, t in enumerate(order) if t == "light")
        assert last_heavy < last_light, order


def test_admission_queue_timeout_rejects():
    with hard_timeout(60, "queue timeout"):
        ctl = AdmissionController(max_concurrent_scans=1,
                                  queue_timeout_s=0.2)
        hold = ctl.admit("t")
        t0 = time.monotonic()
        with pytest.raises(AdmissionRejected) as err:
            ctl.admit("t")
        assert err.value.reason == "queue_timeout"
        assert time.monotonic() - t0 < 5.0
        ctl.release(hold)
        snap = ctl.snapshot()
        assert snap["active_scans"] == 0 and snap["queued_scans"] == 0


def test_server_owned_options_are_rejected(server, fixed_file):
    with hard_timeout(60, "server-owned options"):
        with pytest.raises(ServeError) as err:
            fetch_table(server.address, fixed_file,
                        cache_dir="/tmp/evil", **FIXED_OPTS)
        assert err.value.code == "protocol"
        assert "server-owned" in str(err.value)


# -- faults: structured errors, never hangs ------------------------------


def test_mid_stream_fault_surfaces_as_client_error(server, fixed_file):
    """A storage fault mid-scan (ChaosSource, retries exhausted) must
    reach the client as a ServeError while iterating — the pre-serve
    bridge left the peer blocked in a read here."""
    with hard_timeout(120, "mid-stream fault"):
        scheme = f"chaos{uuid.uuid4().hex[:8]}"
        data = open(fixed_file, "rb").read()
        register_chaos_backend(scheme, data, fail_every=3)
        with pytest.raises(ServeError) as err:
            with stream_scan(server.address, f"{scheme}://input",
                             io_retry_attempts="1",
                             **FIXED_OPTS) as stream:
                for _ in stream:
                    pass
        assert err.value.code == "scan_error"
        assert "injected fault" in str(err.value)


def test_scan_error_before_first_batch_is_structured(server, fixed_file):
    with hard_timeout(60, "pre-stream error"):
        with pytest.raises(ServeError) as err:
            fetch_table(server.address, fixed_file,
                        copybook_contents="       01 R.\n"
                                          "          05 F PIC Q.\n")
        assert err.value.code == "scan_error"
        assert "CopybookSyntaxError" in str(err.value)
        # and the handler survives for the next request
        t = fetch_table(server.address, fixed_file, max_records=1,
                        **FIXED_OPTS)
        assert t.num_rows == 1


def test_stalled_server_read_times_out_client_side(server, fixed_file):
    """A server that produces nothing for longer than the client's read
    timeout surfaces as an OSError/timeout, not an indefinite block."""
    with hard_timeout(120, "client read timeout"):
        scheme = f"slow{uuid.uuid4().hex[:8]}"
        register_chaos_backend(scheme, open(fixed_file, "rb").read(),
                               latency_s=2.0)
        with pytest.raises((OSError, ServeError)):
            with stream_scan(server.address, f"{scheme}://input",
                             read_timeout_s=0.5, **FIXED_OPTS) as stream:
                for _ in stream:
                    pass


def test_bridge_connect_timeout_fails_fast():
    """read_remote against nothing listening raises promptly under its
    RetryPolicy instead of hanging (the satellite fix)."""
    with hard_timeout(60, "bridge connect timeout"):
        import socket

        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead = probe.getsockname()
        probe.close()  # nothing listens here now
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            read_remote(dead, ["/no/such"],
                        connect_retry=RetryPolicy(max_attempts=2,
                                                  base_delay=0.05,
                                                  max_delay=0.1,
                                                  deadline=2.0))
        assert time.monotonic() - t0 < 30.0


def test_bridge_mid_scan_fault_is_a_bridge_error(fixed_file):
    """The compat shim keeps the historical 'bridge error: ...' message
    for scan failures, including MID-stream ones."""
    with hard_timeout(120, "bridge mid-scan fault"):
        srv = BridgeServer().start()
        try:
            scheme = f"bchaos{uuid.uuid4().hex[:8]}"
            register_chaos_backend(scheme, open(fixed_file, "rb").read(),
                                   fail_every=3)
            with pytest.raises(RuntimeError, match="bridge error"):
                read_remote(srv.address, [f"{scheme}://input"],
                            io_retry_attempts="1", **FIXED_OPTS)
        finally:
            srv.stop()


# -- shared warm planes --------------------------------------------------


def test_warm_second_scan_hits_shared_caches(vrl_file, tmp_path):
    """Scan the same remote VRL file twice through one server pinned to
    a `cache_dir`: the trailer's io metrics must show the second scan
    riding the block cache AND the sparse-index store — asserted purely
    client-side, no server shell access."""
    fsspec = pytest.importorskip("fsspec")
    with hard_timeout(180, "warm cache"):
        bucket = f"/serve{uuid.uuid4().hex[:12]}"
        fs = fsspec.filesystem("memory")
        with fs.open(f"{bucket}/data.dat", "wb") as f:
            f.write(open(vrl_file, "rb").read())
        url = f"memory:/{bucket}/data.dat"
        srv = ScanServer(
            server_options={"cache_dir": str(tmp_path / "cache")}).start()
        try:
            def scan_io():
                with stream_scan(srv.address, url, **EXP2_OPTS) as s:
                    rows = sum(b.num_rows for b in s)
                    return rows, s.summary["metrics"]["io"]

            cold_rows, cold_io = scan_io()
            warm_rows, warm_io = scan_io()
            assert cold_rows == warm_rows == 600
            assert cold_io["bytes_fetched"] > 0
            assert warm_io["bytes_fetched"] == 0  # network never touched
            assert warm_io["block_hits"] >= 1
            assert warm_io["index_hits"] >= 1  # no re-index pass
        finally:
            srv.stop()


# -- observability endpoints + progress frames ---------------------------


def test_metrics_and_healthz_scrape(server, fixed_file):
    with hard_timeout(120, "scrape"):
        fetch_table(server.address, fixed_file, tenant="scrape-tenant",
                    max_records=5, **FIXED_OPTS)
        status, headers, body = http_get(server, "/metrics")
        text = body.decode()
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        assert "# HELP cobrix_serve_scans_admitted_total" in text
        assert "# TYPE cobrix_serve_scans_admitted_total counter" in text
        assert 'cobrix_serve_scans_admitted_total{' \
               'tenant="scrape-tenant"}' in text
        assert 'outcome="ok"' in text
        assert "cobrix_serve_first_batch_seconds_bucket" in text
        assert 'cobrix_serve_streamed_bytes_total{' \
               'tenant="scrape-tenant"}' in text

        status, headers, body = http_get(server, "/healthz")
        doc = json.loads(body)
        assert status == 200
        assert headers["Content-Type"] == "application/json"
        assert doc["status"] == "ok"
        assert doc["active_scans"] == 0
        assert "max_concurrent_scans" in doc

        status, _, _ = http_get(server, "/nope")
        assert status == 404


def test_rejection_metrics_carry_reason(fixed_file):
    with hard_timeout(120, "rejection metrics"):
        srv = ScanServer(default_quota=TenantQuota(max_concurrent=1,
                                                   max_queued=0)).start()
        try:
            gate = threading.Event()

            def holder():
                with stream_scan(srv.address, fixed_file, tenant="q",
                                 **FIXED_OPTS) as s:
                    it = iter(s)
                    next(it)
                    gate.set()
                    time.sleep(0.5)
                    for _ in it:
                        pass

            t = threading.Thread(target=holder)
            t.start()
            assert gate.wait(60)
            with pytest.raises(ServeError):
                fetch_table(srv.address, fixed_file, tenant="q",
                            **FIXED_OPTS)
            t.join()
            _, _, body = http_get(srv, "/metrics")
            assert 'cobrix_serve_scans_rejected_total{tenant="q",' \
                   'reason="queue_full"}' in body.decode()
        finally:
            srv.stop()


def test_progress_frames_stream_live(server, fixed_file):
    """Opt-in progress frames arrive as ScanProgress snapshots: bytes
    monotonic, a final done=True, all while batches stream."""
    with hard_timeout(120, "progress frames"):
        snaps = []
        with stream_scan(server.address, fixed_file,
                         progress_callback=snaps.append,
                         progress_interval_s="0",
                         **FIXED_OPTS) as stream:
            batches = sum(1 for _ in stream)
        assert batches > 1
        assert snaps, "no progress frames arrived"
        assert all(isinstance(s, ScanProgress) for s in snaps)
        done_bytes = [s.bytes_done for s in snaps]
        assert done_bytes == sorted(done_bytes)
        assert snaps[-1].done is True
        assert snaps[-1].chunks_done == snaps[-1].chunks_total > 1


def test_progress_frames_absent_unless_requested(server, fixed_file):
    with hard_timeout(120, "no progress by default"):
        with stream_scan(server.address, fixed_file, max_records=5,
                         **FIXED_OPTS) as stream:
            list(stream)
            # the trailer parsed cleanly with no progress callback and
            # no 'P' frames were requested; nothing to assert beyond a
            # clean summary
            assert stream.summary["rows"] == 5


# -- optional flight front-end -------------------------------------------


@pytest.mark.skipif(not flight_available(),
                    reason="pyarrow.flight not importable")
def test_flight_front_end_streams_same_rows(fixed_file):
    import pyarrow.flight as flight

    from cobrix_tpu.serve.flight import FlightScanServer

    with hard_timeout(180, "flight front-end"):
        srv = FlightScanServer().start()
        try:
            client = flight.connect(f"grpc://127.0.0.1:{srv.port}")
            ticket = flight.Ticket(json.dumps(
                {"tenant": "fl", "files": [fixed_file],
                 "options": dict(FIXED_OPTS)}).encode())
            table = client.do_get(ticket).read_all()
            local = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
            assert table.num_rows == local.num_rows
            assert table.schema.names == local.schema.names
            with pytest.raises(flight.FlightError):
                client.do_get(flight.Ticket(b"not json"))
        finally:
            srv.stop()


# -- failed-chunk gaps vs the reorder buffer + byte gate ------------------


def test_executor_signals_failed_chunk_under_partial():
    """A terminally-failed chunk (partial policy) fires the executor's
    on_chunk_failed tap — the signal OrderedBatchEmitter needs to know
    a gap is permanent."""
    from cobrix_tpu.engine.pipeline import PipelineExecutor
    from cobrix_tpu.reader.parameters import ShardErrorPolicy

    def proc(x):
        if x == 1:
            raise ValueError("poison chunk 1")
        return x

    with hard_timeout(60, "failed-chunk signal"):
        ex = PipelineExecutor(2, error_policy=ShardErrorPolicy.PARTIAL)
        failed = []
        ex.on_chunk_failed = failed.append
        out = ex.run([((lambda i=i: i), proc) for i in range(3)])
        assert out == [0, None, 2]
        assert failed == [1]


def test_gap_blocked_emitter_drains_on_failed_chunk_signal():
    """Post-gap tables buffered against the byte gate must drain as
    soon as the gap is declared permanent — NOT stall out the
    byte-wait timeout and fail a healthy chunk."""
    import pyarrow as pa

    from cobrix_tpu.serve.session import OrderedBatchEmitter

    with hard_timeout(60, "gap drain"):
        t = pa.table({"v": list(range(1000))})  # ~8 KB
        budget = int(t.nbytes * 2.5)  # fits 2 buffered tables, not 3
        ctl = AdmissionController(
            default_quota=TenantQuota(max_inflight_bytes=budget),
            byte_wait_timeout_s=20.0)
        written = []
        em = OrderedBatchEmitter(written.append, "t", controller=ctl)
        em.emit(0, t)             # flushes straight through
        em.emit(2, t)             # gap at 1: buffered + charged
        em.emit(3, t)             # buffered + charged (budget now full)
        # another scan of the tenant holds a byte too: without it the
        # buffer would be waiting for itself and is let through
        ctl.acquire_bytes("t", 1)

        blocked_done = threading.Event()

        def emit_blocked():
            em.emit(4, t)         # over budget: blocks on the gate
            blocked_done.set()

        worker = threading.Thread(target=emit_blocked, daemon=True)
        worker.start()
        time.sleep(0.6)           # let it actually block
        assert not blocked_done.is_set()
        t0 = time.monotonic()
        em.emit(1, None)          # chunk 1 failed: the gap is permanent
        assert blocked_done.wait(10), \
            "gate-blocked emit never drained after the failure signal"
        assert time.monotonic() - t0 < 10  # not the 20s no-drain window
        em.finish()
        assert len(written) == 4  # 0,2,3,4 in order; 1 skipped
        ctl.release_bytes("t", 1)
        assert ctl.inflight_bytes("t") == 0


def test_reorder_buffer_never_waits_for_itself():
    """Tables that finish ahead of the next chunk can outweigh the whole
    byte budget (a chunk's table is several times its input on wide
    records). The next chunk comes through the same serialized tap, so
    an emit that waited for this buffer's own bytes would hold it out
    until the no-drain timeout failed a healthy scan."""
    import pyarrow as pa

    from cobrix_tpu.serve.session import OrderedBatchEmitter

    with hard_timeout(60, "self wait"):
        t = pa.table({"v": list(range(1000))})
        ctl = AdmissionController(
            default_quota=TenantQuota(
                max_inflight_bytes=int(t.nbytes * 2.5)),
            byte_wait_timeout_s=20.0)
        written = []
        em = OrderedBatchEmitter(written.append, "t", controller=ctl)
        t0 = time.monotonic()
        for index in (1, 2, 3, 4):  # 4 tables against a 2.5-table budget
            em.emit(index, t)
        assert not written and ctl.inflight_bytes("t") == 4 * t.nbytes
        em.emit(0, t)               # the one they all waited for
        assert time.monotonic() - t0 < 5
        assert len(written) == 5 and ctl.inflight_bytes("t") == 0


def test_batch_callback_delivers_none_for_failed_chunks(fixed_file):
    """read_cobol parity inside ONE partial-policy scan with injected
    chunk failures: every chunk index arrives exactly once (table or
    None), and the delivered tables concatenate to that same read's
    to_arrow()."""
    import pyarrow as pa

    from cobrix_tpu.reader.stream import (ByteRangeSource,
                                          register_stream_backend)

    with hard_timeout(180, "partial batch_callback"):
        payload = open(fixed_file, "rb").read()
        # permanently poison one byte window inside chunk 1 (1 MB
        # chunks): every read touching it fails, across retries too, so
        # exactly that chunk fails terminally under the partial policy
        poison = (1_200_000, 1_300_000)

        class _PoisonSource(ByteRangeSource):
            def __init__(self, name):
                self._name = name

            def size(self):
                return len(payload)

            def read(self, offset, n):
                if offset < poison[1] and offset + n > poison[0]:
                    raise IOError(f"poisoned range {poison}")
                return payload[offset:offset + n]

            def fingerprint(self):
                return "poison-fixture"

            @property
            def name(self):
                return self._name

        scheme = f"poison{uuid.uuid4().hex[:8]}"
        register_stream_backend(scheme, _PoisonSource)
        got = {}

        def on_batch(i, table):
            got[i] = table

        data = read_cobol(f"{scheme}://input", batch_callback=on_batch,
                          shard_error_policy="partial",
                          io_retry_attempts="1", **FIXED_OPTS)
        table = data.to_arrow()
        failures = (data.diagnostics.shard_failures
                    if data.diagnostics else []) or []
        nones = {i for i, tb in got.items() if tb is None}
        assert nones, "the poisoned range produced no chunk failure"
        assert len(nones) == len(failures)
        # the poisoned window sits inside the failed chunk's byte range
        assert any(f.offset_from <= 1_200_000 < (f.offset_to
                   if f.offset_to != -1 else float("inf"))
                   for f in failures), failures
        delivered = [got[i] for i in sorted(got) if got[i] is not None]
        assert pa.concat_tables(delivered).replace_schema_metadata(None) \
            .equals(table.replace_schema_metadata(None))


# -- concurrent multi-tenant ObsContext isolation (PR 8 satellite) -------


def test_concurrent_tenant_obs_isolation(server):
    """Two SIMULTANEOUS streamed scans from different tenants must not
    cross-contaminate trace spans, field costs, or IoStats — the PR 4
    per-read isolation guarantee extended through serve/session.py.

    Each tenant scans a DIFFERENT-SIZED memory:// input with tracing
    and attribution on; any leakage between the two concurrent
    ObsContexts would show up as a wrong per-field value count, a
    wrong remote-byte total, or a foreign span in the merged trace."""
    fsspec = pytest.importorskip("fsspec")
    fs = fsspec.filesystem("memory")
    sizes = {"tenant-a": 2500, "tenant-b": 900}
    urls = {}
    raw_bytes = {}
    for tenant, n in sizes.items():
        payload = generate_exp1(n, seed=len(tenant)).tobytes()
        url = f"memory://iso-{uuid.uuid4().hex}/{tenant}.dat"
        with fs.open(url.replace("memory://", "/"), "wb") as f:
            f.write(payload)
        urls[tenant] = url
        raw_bytes[tenant] = len(payload)

    barrier = threading.Barrier(len(sizes))
    results = {}
    errors = {}

    def scan(tenant):
        try:
            barrier.wait(30)
            with stream_scan(server.address, urls[tenant],
                             tenant=tenant, trace=True,
                             field_costs="true", io_block_mb="0.125",
                             **FIXED_OPTS) as s:
                rows = sum(b.num_rows for b in s)
                results[tenant] = {
                    "rows": rows,
                    "summary": s.summary,
                    "trace": s.chrome_trace(),
                    "trace_id": s.trace_id,
                }
        except Exception as exc:  # pragma: no cover - assertion below
            errors[tenant] = exc

    with hard_timeout(180, "tenant obs isolation"):
        threads = [threading.Thread(target=scan, args=(t,))
                   for t in sizes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors, errors
    assert results["tenant-a"]["trace_id"] != \
        results["tenant-b"]["trace_id"]
    for tenant, n in sizes.items():
        res = results[tenant]
        assert res["rows"] == n
        m = res["summary"]["metrics"]
        # bytes: each scan accounted exactly its own input
        assert m["bytes_read"] == raw_bytes[tenant]
        # IoStats: the remote plane charged this read ONLY its own
        # fetched bytes (block-aligned, so slightly above raw; a leaked
        # context would at least add the OTHER tenant's whole input)
        assert m["io"] is not None
        fetched = m["io"]["bytes_fetched"]
        assert raw_bytes[tenant] <= fetched < raw_bytes[tenant] * 1.2
        # field costs: every attributed field saw exactly this scan's
        # record count — a foreign chunk would inflate it
        fc = m["field_costs"]
        assert fc, "attribution was on"
        assert {v["values"] for v in fc.values()} == {n}
        # trace spans: the merged artifact's root args carry THIS
        # request's identity and record count, and every tagged span
        # agrees on the trace_id
        events = res["trace"]["traceEvents"]
        tagged = {e["args"]["trace_id"] for e in events
                  if (e.get("args") or {}).get("trace_id")}
        assert tagged == {res["trace_id"]}
        roots = [e["args"] for e in events
                 if (e.get("args") or {}).get("records") is not None]
        assert roots and roots[0]["records"] == n
        assert roots[0]["tenant"] == tenant


# -- overload shedding (memory watermark -> degrade -> shed) -------------


@pytest.fixture()
def fake_pressure():
    """Install a process-wide memory monitor driven by a FAKE rss so
    the watermark crossings are deterministic (no gigabyte balloons in
    CI); always uninstalled after."""
    from cobrix_tpu.utils import pressure

    rss = {"value": 0}
    monitor = pressure.set_process_budget(
        1000, degrade_fraction=0.5, shed_fraction=0.9, interval_s=0.0,
        rss_fn=lambda: rss["value"])
    try:
        yield rss, monitor
    finally:
        pressure.set_process_budget(0)


def test_shed_rejects_new_scans_structured(server, fixed_file,
                                           fake_pressure):
    """Past the shed watermark: a structured `overloaded` rejection (no
    SLO burn — it audits as 'rejected'), and scans admitted BEFORE the
    spike still complete."""
    rss, _ = fake_pressure
    with hard_timeout(120, "shed rejection"):
        # a healthy tenant's scan admitted before the pressure spike
        gate = threading.Event()
        done = {}

        def healthy():
            with stream_scan(server.address, fixed_file,
                             tenant="healthy", **FIXED_OPTS) as s:
                it = iter(s)
                first = next(it)
                gate.set()
                done["rows"] = first.num_rows + sum(b.num_rows
                                                    for b in it)

        t = threading.Thread(target=healthy)
        t.start()
        assert gate.wait(60)
        rss["value"] = 950  # past the 90% shed watermark
        with pytest.raises(ServeError) as err:
            fetch_table(server.address, fixed_file, tenant="latecomer",
                        **FIXED_OPTS)
        assert err.value.code == "rejected"
        assert "memory budget" in str(err.value)
        t.join(60)
        # the already-admitted scan finished whole despite the spike
        assert done["rows"] == FIXED_RECORDS
        # the rejection is counted with its own reason
        from cobrix_tpu.obs.metrics import serve_metrics

        assert serve_metrics()["rejected"].value(
            tenant="latecomer", reason="overloaded") >= 1
        # ... and recedes with the pressure
        rss["value"] = 100
        t2 = fetch_table(server.address, fixed_file, tenant="latecomer",
                         max_records=5, **FIXED_OPTS)
        assert t2.num_rows == 5


def test_degrade_halves_io_knobs_and_reports(server, fixed_file,
                                             fake_pressure):
    """Between the degrade and shed watermarks scans still run (and
    parity holds) — with halved read-ahead, flagged on the trailer and
    counted per tenant."""
    rss, _ = fake_pressure
    with hard_timeout(120, "degraded scan"):
        local = read_cobol(fixed_file, **FIXED_OPTS).to_arrow()
        rss["value"] = 700  # between 50% degrade and 90% shed
        with stream_scan(server.address, fixed_file, tenant="squeezed",
                         **FIXED_OPTS) as s:
            t = s.table()
            summary = s.summary
        assert t.equals(local)
        assert summary.get("degraded") is True
        from cobrix_tpu.obs.metrics import serve_metrics

        assert serve_metrics()["degraded"].value(tenant="squeezed") >= 1


def test_degraded_pipeline_shrinks_inflight_window(tmp_path,
                                                   fake_pressure):
    """The engine-side degrade: under pressure the pipeline holds new
    chunks until the in-flight window drops under half, and reports
    it."""
    rss, _ = fake_pressure
    with hard_timeout(120, "pipeline degrade"):
        path = str(tmp_path / "fixed.dat")
        with open(path, "wb") as f:
            f.write(generate_exp1(8000, seed=3).tobytes())
        rss["value"] = 700
        out = read_cobol(path, copybook_contents=EXP1_COPYBOOK,
                         chunk_size_mb="0.5", pipeline_workers="2")
        clean = read_cobol(path, copybook_contents=EXP1_COPYBOOK)
        assert out.to_arrow().equals(clean.to_arrow())
        assert out.metrics.pipeline.get("pressure_degrades", 0) >= 1


def test_queued_scans_shed_lowest_weight_first(fixed_file,
                                               fake_pressure):
    """Under shed pressure the QUEUE drains by eviction: lowest-weight
    tenants' waiters get the structured rejection, higher-weight ones
    keep their place."""
    rss, _ = fake_pressure
    srv = ScanServer(
        max_concurrent_scans=1,
        quotas={"gold": TenantQuota(max_concurrent=1, weight=4.0),
                "bronze": TenantQuota(max_concurrent=1, weight=1.0)},
        queue_timeout_s=30.0).start()
    try:
        with hard_timeout(120, "weighted shed"):
            gate = threading.Event()
            results = {}

            def holder():
                with stream_scan(srv.address, fixed_file, tenant="gold",
                                 **FIXED_OPTS) as s:
                    it = iter(s)
                    next(it)
                    gate.set()
                    time.sleep(1.0)  # hold the only global slot
                    for _ in it:
                        pass
                results["holder"] = "done"

            def waiter(name, tenant):
                try:
                    fetch_table(srv.address, fixed_file, tenant=tenant,
                                max_records=5, **FIXED_OPTS)
                    results[name] = "ok"
                except ServeError as exc:
                    results[name] = str(exc)

            threads = [threading.Thread(target=holder)]
            threads[0].start()
            assert gate.wait(60)
            for name, tenant in (("bronze_w", "bronze"),
                                 ("gold_w", "gold")):
                th = threading.Thread(target=waiter,
                                      args=(name, tenant))
                threads.append(th)
                th.start()
            time.sleep(0.5)  # both queued behind the held slot
            rss["value"] = 950  # spike: shedding evicts bronze first
            # a new arrival triggers the shed sweep and is itself
            # rejected
            with pytest.raises(ServeError):
                fetch_table(srv.address, fixed_file, tenant="probe",
                            max_records=1, **FIXED_OPTS)
            rss["value"] = 100  # recede before the holder releases
            for th in threads:
                th.join(90)
            assert results.get("holder") == "done"
            assert "shed under memory pressure" in results["bronze_w"]
            assert results.get("gold_w") == "ok", results
    finally:
        srv.stop()


def test_server_budget_uninstalled_on_stop(fixed_file):
    """A stopped server's memory budget must not keep throttling the
    process (review-caught: the global watermark outlived the
    server)."""
    from cobrix_tpu.utils.pressure import process_pressure

    srv = ScanServer(memory_budget_mb=1.0).start()
    try:
        assert process_pressure() is not None
        with pytest.raises(ServeError):  # 1 MB budget: sheds instantly
            fetch_table(srv.address, fixed_file, max_records=1,
                        **FIXED_OPTS)
    finally:
        srv.stop()
    assert process_pressure() is None


# -- servecheck smoke (the chunk x workers grid stays behind `slow`) -----


def test_servecheck_quick():
    """The full tool in quick mode: parity, first-batch latency, quota,
    scrape, AND the request-scoped obs section (merged trace, audit
    request_ids, /debug, chaos-slow flight dump)."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "tools/servecheck.py", "--mb", "3"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "request-scoped obs" in proc.stdout


@pytest.mark.slow
def test_servecheck_sweep():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "tools/servecheck.py", "--mb", "6", "--sweep"],
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + proc.stderr
