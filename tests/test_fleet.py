"""Fleet observability plane (ISSUE 12): replica registry heartbeats,
Prometheus exposition parsing/validation/federation, cluster SLO
rollup with multi-window burn, autoscaling signals, the /fleet HTTP
surface, and the zero-overhead contract when fleet mode is off.

The federation edge-case matrix the issue names: stale-heartbeat
expiry, a replica dying mid-scrape (partial view, never a crash or a
hang), clock skew between replicas (the registry reuses PR 4's
common-clock-plus-offset idea via file mtime), and histogram
bucket-boundary mismatch raising a structured error.
"""
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

from cobrix_tpu.fleet.federate import (
    FleetFederator,
    FleetMergeError,
    FleetView,
    ReplicaScrape,
    merge_expositions,
)
from cobrix_tpu.fleet.registry import (
    EXPIRE_FACTOR,
    LIVE_FACTOR,
    FingerprintHeat,
    Heartbeater,
    ReplicaRecord,
    ReplicaRegistry,
    ReplicaStatus,
)
from cobrix_tpu.fleet.signals import derive_signals
from cobrix_tpu.obs import promparse
from cobrix_tpu.obs.metrics import (
    FLEET_GAUGE_MERGE,
    MetricsRegistry,
    default_registry,
    prometheus_text,
    scan_metrics,
    serve_metrics,
    update_process_metrics,
)
from cobrix_tpu.obs.slo import SloTracker, parse_slo

from util import hard_timeout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COPYBOOK = """
        01  R.
            05  KEY    PIC 9(7) COMP.
            05  NAME   PIC X(9).
"""


def make_records(n: int) -> bytes:
    return b"".join(
        i.to_bytes(4, "big") + f"ROW{i % 1000000:06d}".encode("ascii")
        for i in range(n))


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# promparse: round-trip parser + validator (the federation contract)
# ---------------------------------------------------------------------------

def test_own_exposition_is_validator_clean():
    """The exposition every replica serves must parse clean — lint at
    the source, because federation correctness depends on it."""
    m = scan_metrics()
    s = serve_metrics()
    m["scans"].inc()
    m["chunk_latency"].observe(0.02)
    m["cache"].labels(cache="copybook", result="hit").inc()
    s["admitted"].labels(tenant="fleet-test").inc()
    s["queue_wait"].observe(0.004)
    update_process_metrics(open_scans=0)
    text = prometheus_text()
    issues = promparse.validate_text(text)
    assert issues == [], issues
    families = promparse.parse_text(text)
    # round trip: render(parse(x)) parses back identical
    assert promparse.parse_text(promparse.render(families)) == families
    assert families["cobrix_scans_total"].kind == "counter"
    assert families["cobrix_chunk_latency_seconds"].kind == "histogram"


def test_validator_catches_structural_breaks():
    dup = "# TYPE x counter\n# TYPE x counter\nx 1\nx 1\n"
    issues = promparse.validate_text(dup)
    assert any("declared twice" in i for i in issues)
    assert any("duplicate series" in i for i in issues)

    noncum = ("# TYPE h histogram\n"
              'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\n'
              'h_bucket{le="+Inf"} 6\nh_sum 1\nh_count 6\n')
    assert any("not cumulative" in i
               for i in promparse.validate_text(noncum))

    inf_mismatch = ("# TYPE h histogram\n"
                    'h_bucket{le="+Inf"} 6\nh_sum 1\nh_count 7\n')
    assert any("disagrees with _count" in i
               for i in promparse.validate_text(inf_mismatch))

    bad_escape = '# TYPE c counter\nc{a="x\\q"} 1\n'
    assert any("escape" in i for i in promparse.validate_text(bad_escape))

    late_type = "c 1\n# TYPE c counter\nc{a=\"y\"} 1\n"
    assert any("after its samples" in i
               for i in promparse.validate_text(late_type))


def test_label_escaping_round_trips():
    fam = promparse.Family(name="c", kind="counter")
    nasty = 'quo"te\\back\nline'
    fam.samples.append(promparse.Sample("c", (("path", nasty),), 2.0))
    text = promparse.render({"c": fam})
    back = promparse.parse_text(text)
    assert back["c"].samples[0].labels == (("path", nasty),)
    assert promparse.validate_text(text) == []


def test_histogram_bucket_boundaries_pinned_per_registry():
    """The federation invariant at its source: one metric name = one
    bucket layout, asserted at registration."""
    r = MetricsRegistry()
    r.histogram("h", buckets=(1.0, 2.0))
    r.histogram("h", buckets=(2.0, 1.0))  # same set, different order: ok
    with pytest.raises(ValueError, match="federation"):
        r.histogram("h", buckets=(1.0, 3.0))


def test_every_registered_gauge_declares_fleet_merge():
    """Adding a gauge must come with a fleet merge policy (sum/max) —
    the declaration lives next to the metric definitions."""
    from cobrix_tpu.obs.metrics import (Gauge, process_metrics,
                                        stream_metrics)

    scan_metrics()
    serve_metrics()
    stream_metrics()
    process_metrics()
    undeclared = [
        name for name, metric in default_registry()._metrics.items()
        if isinstance(metric, Gauge) and name not in FLEET_GAUGE_MERGE]
    assert undeclared == [], (
        f"gauges without a FLEET_GAUGE_MERGE policy: {undeclared}")


# ---------------------------------------------------------------------------
# replica registry: heartbeats, liveness, corruption, clock skew
# ---------------------------------------------------------------------------

def _registry(tmp_path, interval_s=0.5):
    return ReplicaRegistry(str(tmp_path / "fleet"),
                           interval_s=interval_s)


def _record(rid="r0", interval_s=0.5, **kw):
    now = time.time()
    defaults = dict(replica_id=rid, pid=1, host="h",
                    http_address=["127.0.0.1", 1],
                    started_at=now - 10, heartbeat_at=now,
                    interval_s=interval_s)
    defaults.update(kw)
    return ReplicaRecord(**defaults)


def test_heartbeat_roundtrip_and_liveness_states(tmp_path):
    reg = _registry(tmp_path)
    reg.write(_record("alpha", active_scans=2,
                      heat=[{"key": "plan:x", "count": 4}]))
    statuses = reg.read()
    assert [s.record.replica_id for s in statuses] == ["alpha"]
    assert statuses[0].state == "live"
    assert statuses[0].record.active_scans == 2
    assert statuses[0].record.heat == [{"key": "plan:x", "count": 4}]
    path = reg.path_for("alpha")
    # stale: older than LIVE_FACTOR intervals but unexpired
    stale_age = 0.5 * (LIVE_FACTOR + 1)
    os.utime(path, (time.time() - stale_age, time.time() - stale_age))
    assert reg.read()[0].state == "stale"
    # expired: past EXPIRE_FACTOR intervals -> gone from the view
    old = time.time() - 0.5 * (EXPIRE_FACTOR + 2)
    os.utime(path, (old, old))
    assert reg.read() == []
    # unregister removes the file entirely
    reg.write(_record("alpha"))
    reg.unregister("alpha")
    assert reg.read() == []
    assert not os.path.exists(path)


def test_corrupt_heartbeat_is_quarantined_never_a_phantom(tmp_path):
    from cobrix_tpu.io.integrity import corruption_counter

    reg = _registry(tmp_path)
    reg.write(_record("good"))
    reg.write(_record("evil"))
    # valid JSON, wrong crc: flipped payload INSIDE a well-formed file
    path = reg.path_for("evil")
    doc = json.loads(open(path).read())
    doc["active_scans"] = 999
    open(path, "w").write(json.dumps(doc))
    before = corruption_counter().value(plane="fleet")
    statuses = reg.read()
    assert [s.record.replica_id for s in statuses] == ["good"]
    assert corruption_counter().value(plane="fleet") == before + 1
    assert not os.path.exists(path)  # quarantined away
    q_dir = os.path.join(reg.root, "quarantine")
    assert os.path.isdir(q_dir) and os.listdir(q_dir)
    # plain garbage is skipped too (second read: file already gone)
    open(reg.path_for("noise"), "w").write("\x00\x01 not json")
    assert [s.record.replica_id for s in reg.read()] == ["good"]


def test_clock_skew_surfaces_instead_of_lying(tmp_path):
    """A replica with a wall clock an hour ahead still heartbeats
    fresh mtimes: liveness is judged on the COMMON clock (file mtime,
    PR 4's shared-axis idea) and the writer's offset is surfaced as
    clock_skew_s — corrected uptime, not a phantom-stale replica."""
    reg = _registry(tmp_path)
    skew = 3600.0
    now = time.time()
    reg.write(_record("skewed", heartbeat_at=now + skew,
                      started_at=now + skew - 50))
    status = reg.read()[0]
    assert status.state == "live"          # mtime fresh -> live
    assert abs(status.clock_skew_s - skew) < 5.0
    doc = status.as_dict()
    # started_at corrected by the offset: ~50s of uptime, not -59min
    assert 40 < doc["uptime_s"] < 70


def test_heartbeater_thread_writes_and_unregisters(tmp_path):
    reg = _registry(tmp_path, interval_s=0.05)
    beats = []

    def record_fn():
        beats.append(1)
        return _record("beating", interval_s=0.05)

    hb = Heartbeater(reg, record_fn, interval_s=0.05).start()
    with hard_timeout(30, "heartbeater"):
        deadline = time.monotonic() + 10
        while len(beats) < 3 and time.monotonic() < deadline:
            time.sleep(0.02)
    assert len(beats) >= 3
    assert reg.read()[0].record.replica_id == "beating"
    hb.stop(unregister=True)
    assert reg.read() == []


def test_fingerprint_heat_bounded():
    heat = FingerprintHeat(max_keys=16)
    for i in range(100):
        heat.bump([f"file:f{i}"])
    for _ in range(5):
        heat.bump(["plan:hot"])
    top = heat.top(3)
    assert top[0] == {"key": "plan:hot", "count": 5}
    assert len(heat._counts) <= 16


# ---------------------------------------------------------------------------
# federation merge: sums, declared gauge policies, bucket mismatch,
# partial views
# ---------------------------------------------------------------------------

def _exposition(scans: int, rss: float, age: float,
                buckets=((0.1, 1), (1.0, 2))) -> str:
    text = ("# TYPE cobrix_scans_total counter\n"
            f"cobrix_scans_total {scans}\n"
            "# TYPE cobrix_process_rss_bytes gauge\n"
            f"cobrix_process_rss_bytes {rss}\n"
            "# TYPE cobrix_stream_watermark_age_seconds gauge\n"
            f"cobrix_stream_watermark_age_seconds {age}\n"
            "# TYPE cobrix_slo_good_total counter\n"
            'cobrix_slo_good_total{slo="error_rate",tenant="t1"}'
            " 3\n"
            "# TYPE w histogram\n")
    cum = 0
    for le, n in buckets:
        cum += n
        text += f'w_bucket{{le="{le}"}} {cum}\n'
    text += (f'w_bucket{{le="+Inf"}} {cum}\n'
             f"w_sum 0.5\nw_count {cum}\n")
    return text


def test_merge_counters_sum_gauges_by_policy_histograms_bucketwise():
    per = {"a": promparse.parse_text(_exposition(5, 100, 7.0)),
           "b": promparse.parse_text(_exposition(9, 50, 3.0))}
    merged = merge_expositions(per)
    # counters: exact sum + per-replica labeled series
    scans = merged["cobrix_scans_total"]
    assert scans.value(()) == 14.0
    assert scans.value((("replica", "a"),)) == 5.0
    assert scans.value((("replica", "b"),)) == 9.0
    # declared gauge policies: rss sums, watermark age is a max
    assert merged["cobrix_process_rss_bytes"].value(()) == 150.0
    assert merged["cobrix_stream_watermark_age_seconds"] \
        .value(()) == 7.0
    # labeled counters keep their label sets
    assert merged["cobrix_slo_good_total"].value(
        (("slo", "error_rate"), ("tenant", "t1"))) == 6.0
    # histograms merge bucket-wise; +Inf == _count on the cluster series
    w = merged["w"]
    assert w.value((("le", "+Inf"),), suffix="_bucket") == 6.0
    assert w.value((), suffix="_count") == 6.0
    # and the merged exposition is itself scrapeable + lint-clean
    text = promparse.render(merged)
    assert promparse.validate_text(text) == []
    assert promparse.parse_text(text)["cobrix_scans_total"] \
        .value(()) == 14.0


def test_histogram_bucket_mismatch_raises_structured_error():
    per = {"a": promparse.parse_text(_exposition(1, 1, 1)),
           "b": promparse.parse_text(
               _exposition(1, 1, 1, buckets=((0.2, 1),)))}
    with pytest.raises(FleetMergeError) as exc:
        merge_expositions(per)
    assert exc.value.metric == "w"
    assert set(exc.value.replicas) == {"a", "b"}
    assert "bucket boundaries differ" in str(exc.value)


def _fed(tmp_path, responses: dict, interval_s=0.5):
    """A federator whose fetch is a dict lookup: replica_id ->
    (metrics_text, healthz, slo) or an Exception to raise."""
    reg = ReplicaRegistry(str(tmp_path / "fleet"), interval_s=interval_s)
    for rid in responses:
        reg.write(_record(rid, interval_s=interval_s))

    def fetch(status):
        r = responses[status.record.replica_id]
        if isinstance(r, Exception):
            raise r
        return r

    return FleetFederator(reg, timeout_s=1.0, cache_ttl_s=0.0,
                          fetcher=fetch)


def test_replica_death_mid_scrape_yields_partial_view(tmp_path):
    """A SIGKILLed replica whose heartbeat has not expired yet answers
    the scrape with a connection error: the fleet view stays PARTIAL
    and every product (exposition, slo, signals) still works."""
    fed = _fed(tmp_path, {
        "up": (_exposition(5, 1, 1), {"active_scans": 0}, {"slo": {}}),
        "dead": ConnectionRefusedError("connection refused"),
    })
    with hard_timeout(60, "partial scrape"):
        view = fed.view()
    assert len(view.replicas) == 2
    assert len(view.reachable()) == 1
    doc = view.replicas_doc()
    dead = [r for r in doc["replicas"]
            if r["replica_id"] == "dead"][0]
    assert dead["reachable"] is False
    assert "ConnectionRefusedError" in dead["scrape_error"]
    # the exposition only carries the reachable replica — no crash
    text = fed.cluster_exposition(view)
    assert 'replica="up"' in text and "dead" not in text
    rollup = fed.slo_rollup(view)
    assert rollup["replicas_reporting"] == 1
    sig = derive_signals(view, history=fed.history())
    assert sig["known_replicas"] == 2


def test_stale_heartbeat_expires_out_of_the_scrape_set(tmp_path):
    fed = _fed(tmp_path, {
        "fresh": (_exposition(1, 1, 1), {}, {"slo": {}}),
        "gone": (_exposition(1, 1, 1), {}, {"slo": {}}),
    })
    old = time.time() - 0.5 * (EXPIRE_FACTOR + 2)
    os.utime(fed.registry.path_for("gone"), (old, old))
    view = fed.view()
    assert [r.replica_id for r in view.replicas] == ["fresh"]


def test_slo_rollup_sums_per_replica_documents(tmp_path):
    slo_doc = lambda good, bad: {"slo": {  # noqa: E731
        "error_rate": {
            "kind": "error_rate", "threshold": 0.01,
            "objective": 0.99, "good": good, "bad": bad,
            "ratio": None, "burning": bad > 0,
            "burn_fast": {"window_s": 60.0, "good": good, "bad": bad},
            "burn_slow": {"window_s": 600.0, "good": good,
                          "bad": bad}}}}
    fed = _fed(tmp_path, {
        "a": (_exposition(1, 1, 1), {}, slo_doc(8, 2)),
        "b": (_exposition(1, 1, 1), {}, slo_doc(5, 0)),
    })
    rollup = fed.slo_rollup()
    er = rollup["slo"]["error_rate"]
    assert (er["good"], er["bad"]) == (13, 2)
    assert er["replicas"]["a"] == {"good": 8, "bad": 2,
                                   "burning": True}
    # fleet burn over the budget: 2/15 bad over a 1% budget
    assert er["burn_fast"]["burn"] == pytest.approx(
        (2 / 15) / 0.01, rel=1e-3)
    assert er["burning"] is True
    # per-tenant totals come from the scraped counter series (3 per
    # replica in the synthetic exposition)
    assert er["tenants"]["t1"]["good"] == 6


# ---------------------------------------------------------------------------
# multi-window SLO burn
# ---------------------------------------------------------------------------

def test_multiwindow_burn_fast_vs_slow():
    clock = [1000.0]
    tracker = SloTracker([parse_slo("error_rate=0.1")],
                         registry=MetricsRegistry(),
                         fast_window_s=60, slow_window_s=600,
                         clock=lambda: clock[0])

    class R:
        outcome = "ok"
        tenant = "t"
        resume_of = ""
        follow = False
        slo_breaches = []

    # old window: 20 good scans, 10 minutes ago
    for _ in range(20):
        tracker.observe(R())
    clock[0] += 590
    bad = R()
    bad.outcome = "error"
    for _ in range(5):
        tracker.observe(bad)
    status = tracker.status()["error_rate"]
    # fast window: only the 5 errors -> ratio 1.0, burn 10x
    assert status["burn_fast"]["bad"] == 5
    assert status["burn_fast"]["good"] == 0
    assert status["burn_fast"]["burn"] == pytest.approx(10.0)
    # slow window: 5 bad / 25 seen -> burn 2x
    assert status["burn_slow"]["good"] == 20
    assert status["burn_slow"]["burn"] == pytest.approx(2.0)
    # beyond the slow window everything ages out
    clock[0] += 700
    status = tracker.status()["error_rate"]
    assert status["burn_slow"]["ratio"] is None
    assert status["good"] == 20  # lifetime totals keep history


# ---------------------------------------------------------------------------
# autoscaling signals
# ---------------------------------------------------------------------------

def _view_with(queue_buckets, rejections=0, active=0, cap=2,
               queued=0, n=2, pressure="ok"):
    text = "# TYPE cobrix_serve_queue_wait_seconds histogram\n"
    cum = 0
    for le, c in queue_buckets:
        cum += c
        text += (f'cobrix_serve_queue_wait_seconds_bucket'
                 f'{{le="{le}"}} {cum}\n')
    text += (f'cobrix_serve_queue_wait_seconds_bucket{{le="+Inf"}} '
             f"{cum}\n"
             f"cobrix_serve_queue_wait_seconds_sum 1\n"
             f"cobrix_serve_queue_wait_seconds_count {cum}\n")
    if rejections:
        text += ("# TYPE cobrix_serve_scans_rejected_total counter\n"
                 f'cobrix_serve_scans_rejected_total'
                 f'{{reason="queue_full",tenant="t"}} {rejections}\n')
    view = FleetView(scraped_at=time.time())
    for i in range(n):
        rec = ReplicaRecord(replica_id=f"r{i}",
                            max_concurrent_scans=cap,
                            active_scans=active, queued_scans=queued,
                            pressure=pressure)
        view.replicas.append(ReplicaScrape(
            status=ReplicaStatus(record=rec, state="live", age_s=0.1,
                                 clock_skew_s=0.0),
            families=promparse.parse_text(text),
            healthz={}, slo={}))
    return view


def test_signals_scale_up_on_queue_wait():
    calm = _view_with([("0.01", 2)])
    hot = _view_with([("0.01", 2), ("2.5", 10)], active=2, queued=4)
    history = [(time.monotonic() - 10, calm), (time.monotonic(), hot)]
    sig = derive_signals(hot, history=history, queue_wait_target_s=0.5)
    assert sig["desired_replicas"] > sig["live_replicas"]
    assert any("queue_wait" in r for r in sig["reasons"])
    assert sig["inputs"]["queue_wait_p90_s"] == 2.5
    assert sig["actuates"] is False


def test_signals_scale_up_on_rejections_and_pressure():
    base = _view_with([("0.01", 2)])
    shed = _view_with([("0.01", 2)], rejections=3, pressure="shed")
    history = [(time.monotonic() - 10, base), (time.monotonic(), shed)]
    sig = derive_signals(shed, history=history)
    assert sig["desired_replicas"] > sig["live_replicas"]
    joined = " ".join(sig["reasons"])
    assert "rejection" in joined and "pressure" in joined


def test_signals_scale_down_only_when_fully_idle():
    idle = _view_with([("0.01", 2)], active=0, n=3)
    history = [(time.monotonic() - 10, idle), (time.monotonic(), idle)]
    sig = derive_signals(idle, history=history)
    assert sig["desired_replicas"] == 2  # one step down, min 1
    busy = _view_with([("0.01", 2)], active=1, n=3)
    sig2 = derive_signals(
        busy, history=[(time.monotonic() - 10, busy),
                       (time.monotonic(), busy)])
    assert sig2["desired_replicas"] == 3  # 50% utilization: steady


def test_signals_without_baseline_stay_conservative():
    """Lifetime counters must not read as present pressure on the
    very first scrape (no window baseline)."""
    view = _view_with([("2.5", 100)], rejections=50)
    sig = derive_signals(view, history=[(time.monotonic(), view)])
    assert sig["inputs"]["window_has_baseline"] is False
    assert sig["inputs"]["queue_wait_p90_s"] is None
    assert sig["inputs"]["rejections_in_window"] == 0
    assert sig["desired_replicas"] == sig["live_replicas"]


def test_signals_baseline_falls_back_beyond_window():
    """A consumer polling SLOWER than the fast window (a 60s+
    autoscaler loop) must still get rate signals: the delta baseline
    falls back to the newest prior snapshot outside the window, and
    the observed span is reported."""
    calm = _view_with([("0.01", 2)])
    hot = _view_with([("0.01", 2), ("2.5", 10)], active=2, queued=4)
    history = [(time.monotonic() - 300, calm), (time.monotonic(), hot)]
    sig = derive_signals(hot, history=history, queue_wait_target_s=0.5,
                         fast_window_s=60.0)
    assert sig["inputs"]["window_has_baseline"] is True
    assert sig["inputs"]["window_observed_s"] >= 299
    assert sig["inputs"]["queue_wait_p90_s"] == 2.5
    assert sig["desired_replicas"] > sig["live_replicas"]


def test_signals_cache_affinity_hints():
    view = _view_with([("0.01", 1)], n=2)
    view.replicas[0].status.record.heat = [
        {"key": "plan:abc", "count": 9}]
    view.replicas[1].status.record.heat = [
        {"key": "plan:abc", "count": 2},
        {"key": "file:/x", "count": 5}]
    sig = derive_signals(view, history=[])
    hints = {h["key"]: h for h in sig["cache_affinity"]}
    assert hints["plan:abc"]["replica"] == "r0"
    assert hints["plan:abc"]["fleet_count"] == 11
    assert hints["file:/x"]["replica"] == "r1"


# ---------------------------------------------------------------------------
# serve integration: the /fleet surface on a live (single-replica) server
# ---------------------------------------------------------------------------

def _http_json(addr, path):
    import urllib.request

    with urllib.request.urlopen(
            f"http://{addr[0]}:{addr[1]}{path}", timeout=10) as r:
        return json.loads(r.read())


def _http_text(addr, path):
    import urllib.request

    with urllib.request.urlopen(
            f"http://{addr[0]}:{addr[1]}{path}", timeout=10) as r:
        return r.read().decode()


def test_fleet_server_serves_cluster_view(tmp_path):
    from cobrix_tpu.serve import ScanServer, fetch_table

    data = tmp_path / "feed.dat"
    data.write_bytes(make_records(500))
    with hard_timeout(120, "fleet server"):
        srv = ScanServer(
            port=0, http_port=0,
            server_options={"cache_dir": str(tmp_path / "cache")},
            slos=["error_rate=0.01"],
            fleet=True, replica_id="solo",
            heartbeat_interval_s=0.2).start()
        try:
            table = fetch_table(srv.address, str(data), tenant="etl",
                                copybook_contents=COPYBOOK)
            assert table.num_rows == 500
            # wait for the post-scan heartbeat (heat + counters)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                doc = _http_json(srv.http_address, "/fleet/replicas")
                heat = doc["replicas"][0].get("heat") or []
                if heat:
                    break
                time.sleep(0.1)
            assert doc["live"] == 1
            rep = doc["replicas"][0]
            assert rep["replica_id"] == "solo"
            assert rep["state"] == "live" and rep["reachable"]
            keys = {h["key"] for h in rep["heat"]}
            assert f"file:{data}" in keys
            assert any(k.startswith("plan:") for k in keys)
            # federated exposition: validator-clean; the single
            # replica's cluster totals equal its own /metrics
            fleet_text = _http_text(srv.http_address, "/fleet/metrics")
            assert promparse.validate_text(fleet_text) == []
            fleet = promparse.parse_text(fleet_text)
            own = promparse.parse_text(
                _http_text(srv.http_address, "/metrics"))
            own_admitted = own["cobrix_serve_scans_admitted_total"] \
                .value((("tenant", "etl"),))
            assert own_admitted >= 1
            assert fleet["cobrix_serve_scans_admitted_total"].value(
                (("tenant", "etl"),)) == own_admitted
            assert fleet["cobrix_serve_scans_admitted_total"].value(
                (("replica", "solo"), ("tenant", "etl"))) \
                == own_admitted
            # /fleet/slo matches /debug/slo
            fleet_slo = _http_json(srv.http_address, "/fleet/slo")
            own_slo = _http_json(srv.http_address, "/debug/slo")
            assert fleet_slo["slo"]["error_rate"]["good"] \
                == own_slo["slo"]["error_rate"]["good"] >= 1
            # signals answer and never actuate
            sig = _http_json(srv.http_address, "/fleet/signals")
            assert sig["live_replicas"] == 1
            assert sig["actuates"] is False
            hb_path = srv._fleet["registry"].path_for("solo")
            assert os.path.exists(hb_path)
        finally:
            srv.stop()
        # clean stop unregisters the replica record
        assert not os.path.exists(hb_path)


def test_fleet_mode_requires_shared_cache_dir():
    from cobrix_tpu.serve import ScanServer

    with pytest.raises(ValueError, match="cache_dir"):
        ScanServer(port=0, enable_http=False, fleet=True)


def test_fleet_off_is_zero_overhead_counter_asserted(tmp_path):
    """Fleet mode off: the fleet package is never imported, no
    heartbeat file exists, HEARTBEAT_WRITES never moves — asserted in
    a FRESH interpreter so this test is immune to import order."""
    data = tmp_path / "feed.dat"
    data.write_bytes(make_records(50))
    cache = tmp_path / "cache"
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
from cobrix_tpu.serve import ScanServer, fetch_table
srv = ScanServer(port=0, http_port=0,
                 server_options={{"cache_dir": {str(cache)!r}}}).start()
t = fetch_table(srv.address, {str(data)!r}, tenant="t",
                copybook_contents={COPYBOOK!r})
srv.stop()
assert t.num_rows == 50
import os
assert not any(m.startswith("cobrix_tpu.fleet") for m in sys.modules)
assert not os.path.exists({str(cache / 'fleet')!r})
import urllib.request, urllib.error
print("NOFLEET_OK")
"""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    with hard_timeout(180, "zero-overhead subprocess"):
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True,
                             timeout=170)
    assert out.returncode == 0 and "NOFLEET_OK" in out.stdout, (
        out.stdout, out.stderr[-2000:])


# ---------------------------------------------------------------------------
# tools: scanlog --merge, fleetcheck (the tier-1 smoke)
# ---------------------------------------------------------------------------

def test_scanlog_merge_follows_request_across_replicas(tmp_path):
    recs = {
        "r1.log": [
            {"request_id": "req-A", "trace_id": "abc123" * 5,
             "tenant": "etl", "outcome": "error", "ts": 100.0,
             "rows": 5, "e2e_s": 0.2},
            {"request_id": "req-B", "trace_id": "zzz" * 10,
             "tenant": "bi", "outcome": "ok", "ts": 102.0, "rows": 7},
        ],
        "r2.log": [
            {"request_id": "req-A2", "trace_id": "abc123" * 5,
             "tenant": "etl", "outcome": "ok", "ts": 101.0,
             "rows": 5, "resume_of": "req-A"},
        ],
    }
    for name, rows in recs.items():
        with open(tmp_path / name, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    # one --request-id query follows the failover tie across replicas
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanlog.py"),
         "tail", "--merge", str(tmp_path / "r*.log"),
         "--request-id", "req-A"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "r1" in out.stdout and "r2" in out.stdout
    assert "resume_of=req-A" in out.stdout
    assert "req-B" not in out.stdout
    # merged summary: per-replica lines + the fleet-wide rollup
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanlog.py"),
         "summary", str(tmp_path / "r1.log"), str(tmp_path / "r2.log")],
        capture_output=True, text=True, env=env, timeout=60)
    assert out2.returncode == 0
    assert "replica r1" in out2.stdout and "replica r2" in out2.stdout
    assert "fleet-wide" in out2.stdout
    # single-log invocation unchanged (no replica column)
    out3 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scanlog.py"),
         "tail", str(tmp_path / "r1.log")],
        capture_output=True, text=True, env=env, timeout=60)
    assert out3.returncode == 0
    assert not out3.stdout.startswith("r1 ")


def test_fleetcheck_three_replica_smoke():
    """The ISSUE 12 acceptance harness: 3 subprocess replicas, one
    cache_dir — byte-exact federated counters, SLO rollup parity,
    signals responding to induced pressure, zero-overhead off path,
    and SIGKILL degrading the view within a heartbeat interval."""
    fleetcheck = _load_tool("fleetcheck")
    with hard_timeout(420, "fleetcheck"):
        assert fleetcheck.check_fleet(sweep=False)
