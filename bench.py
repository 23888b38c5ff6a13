"""Benchmark: exp3 multisegment-wide scan throughput (MB/s).

Reproduces the reference's north-star workload (BASELINE.md exp3:
RDW variable-length multisegment file; wide 'C' segments with
STRATEGY-DETAIL OCCURS 2000 of COMP + COMP-3, 16,068-byte records,
interleaved with 64-byte 'P' contact segments). Reference single-core
throughput is ~8.0 MB/s (performance/exp3_multiseg_wide.csv); the
vs_baseline field is measured MB/s / 8.0.

The HEADLINE is the honest end-to-end conversion: file -> RDW framing
-> segment split -> kernel decode -> Arrow table, timed exactly like
the reference job produced Parquet columns. The kernel-only framing +
decode measurement (no Arrow assembly; the number earlier rounds
headlined) stays alongside as `decode_only` — comparing IT against the
full-conversion baseline overstates, so `vs_baseline` uses the
end-to-end value. Data generation and jit warmup are excluded.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_MBPS = 8.0  # exp3, 1 executor (BASELINE.md)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _calibrate_roofline():
    """One-time host memory-bandwidth calibration (obs/roofline.py;
    cached on disk so later rounds and per-read metrics reuse it).
    Returns bytes/s or None — a failed calibration must never sink the
    bench."""
    try:
        from cobrix_tpu.obs.roofline import measured_bandwidth

        t0 = time.perf_counter()
        bw = measured_bandwidth()
        _log(f"roofline: host memory bandwidth {bw / 1e9:.1f} GB/s "
             f"({time.perf_counter() - t0:.1f}s; cached)")
        return bw
    except Exception as exc:
        _log(f"roofline calibration failed: {exc}")
        return None


def _roofline_field(mbps) -> dict:
    """{'calibrated_GBps', 'fraction'} anchoring a measured MB/s against
    the cached calibration — the decode-throughput-law view (arxiv
    2606.22423): regressions visible as a fraction of the hardware
    limit, not just MB/s. None when uncalibrated."""
    try:
        from cobrix_tpu.obs.roofline import cached_bandwidth

        bw = cached_bandwidth()
        if not bw or not mbps:
            return None
        return {"calibrated_GBps": round(bw / 1e9, 2),
                "fraction": round(mbps * 1024 * 1024 / bw, 4)}
    except Exception:
        return None


def _top_fields_profile(path, kw, n=5):
    """Top-N per-field costs from ONE attribution-enabled read of the
    same workload (cobrix_tpu.obs.fieldcost). Run SEPARATELY from the
    timed runs so the headline numbers never carry attribution
    overhead; the table makes the BENCH trajectory self-describing
    about WHICH columns the time goes to."""
    try:
        from cobrix_tpu import read_cobol
        from cobrix_tpu.obs.fieldcost import top_fields

        out = read_cobol(path, field_costs="true", **kw)
        out.to_arrow()
        costs = out.metrics.field_costs if out.metrics else None
        return top_fields(costs, n) if costs else None
    except Exception as exc:
        _log(f"field-cost profile failed: {exc}")
        return None


def run_device_query(mb_target: float, platform: str) -> dict:
    """The device-resident query benchmark: decode + aggregate the exp3
    wide-segment numeric plane ON the device; only scalar aggregates cross
    the link back (parallel/query.py — the pipeline the reference needs a
    whole Spark stage after the Cobrix scan to express).

    Phases reported separately: host RDW framing + [n, extent] pack,
    H2D streaming, device decode+reduce, and the pipelined end-to-end
    rate over the total file bytes.
    """
    from cobrix_tpu import native
    from cobrix_tpu.parallel import DeviceAggregator, merge_aggregates
    from cobrix_tpu.reader.parameters import (
        MultisegmentParameters,
        ReaderParameters,
    )
    from cobrix_tpu.reader.var_len_reader import VarLenReader
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK, generate_exp3

    params = ReaderParameters(
        is_record_sequence=True,
        multisegment=MultisegmentParameters(
            segment_id_field="SEGMENT-ID",
            segment_id_redefine_map={"C": "STATIC_DETAILS",
                                     "P": "CONTACTS"}))
    reader = VarLenReader(EXP3_COPYBOOK, params)
    # backend resolves per platform: fused Pallas kernel on TPU, the plain
    # XLA program elsewhere (parallel/sharded.resolve_device_backend)
    agg = DeviceAggregator(reader.copybook, columns=["NUM1", "NUM2"],
                           active_segment="STATIC_DETAILS")
    _log(f"device query decode backend: {agg.decoder.backend}")

    est_per_record = 16072 * 0.33 + 68 * 0.67
    n_records = max(64, int(mb_target * 1024 * 1024 / est_per_record))
    raw = generate_exp3(n_records, seed=100)
    total_mb = len(raw) / (1024 * 1024)
    rs = agg.record_extent
    # ~32MB blocks amortise the fixed cost of a transfer and a launch
    block = int(os.environ.get(
        "BENCH_DEVICE_BLOCK", str(max(512, (32 * 1024 * 1024 // rs + 255)
                                      // 256 * 256))))

    def frame_and_pack():
        """RDW scan + gather the wide 'C' records into fixed [block, rs]
        matrices (host side of the pipeline)."""
        offsets, lengths = native.rdw_scan(raw, big_endian=False)
        pos = np.nonzero(lengths >= 1000)[0]
        coffs = offsets[pos]
        buf = np.frombuffer(raw, dtype=np.uint8)
        mats = []
        for i in range(0, len(coffs), block):
            o = coffs[i:i + block]
            mats.append(buf[o[:, None] + np.arange(rs)[None, :]])
        return mats

    # warmup: compile the aggregate program on one block shape
    t0 = time.perf_counter()
    mats = frame_and_pack()
    pack_s = time.perf_counter() - t0
    x, n = agg.put(mats[0], block=block)
    agg.aggregate_device(x, n)
    _log(f"device query warmup (incl. compile): "
         f"{time.perf_counter() - t0:.1f}s; {len(mats)} blocks of {block}")

    c_bytes = sum(m.nbytes for m in mats)

    # phase timing (synchronized per block)
    h2d_s = comp_s = 0.0
    for m in mats:
        t0 = time.perf_counter()
        x, n = agg.put(m, block=block)
        x.block_until_ready()
        h2d_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        agg.aggregate_device(x, n)
        comp_s += time.perf_counter() - t0

    # end-to-end (pipelined: submit all blocks, fetch at the end)
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        pend = []
        for m in frame_and_pack():
            x, n = agg.put(m, block=block)
            pend.append(agg.submit(x, n))
        parts = [agg.fetch(p) for p in pend]
        merged = merge_aggregates(parts)
        times.append(time.perf_counter() - t0)
    e2e = min(times)
    d2h_bytes = len(parts) * sum(28 + len(k) for k in parts[0]) + 4

    # one profiler trace artifact of a single aggregate step (SURVEY.md §5
    # tracing row): loadable in TensorBoard/XProf; recorded in the JSON
    from cobrix_tpu.profiling import profile_trace

    trace_dir = os.environ.get("BENCH_TRACE_DIR", "bench_trace")
    with profile_trace(trace_dir):
        x, n = agg.put(mats[0], block=block)
        agg.aggregate_device(x, n)

    # projected single-column variant: the NUM1-only query byte-projects
    # to ~half the record (DeviceAggregator._build_byte_projection), so
    # H2D bytes shrink by the projection ratio
    agg1 = DeviceAggregator(reader.copybook, columns=["NUM1"],
                            active_segment="STATIC_DETAILS")
    x, n1 = agg1.put(mats[0], block=block)
    agg1.aggregate_device(x, n1)  # compile
    times1 = []
    for _ in range(2):
        t0 = time.perf_counter()
        pend = [agg1.submit(*agg1.put(m, block=block)) for m in mats]
        parts1 = [agg1.fetch(p) for p in pend]
        times1.append(time.perf_counter() - t0)
    proj_bytes = (len(agg1.gather_index)
                  if agg1.gather_index is not None else rs)
    proj = {
        "end_to_end_MBps": round(total_mb / min(times1), 1),
        "projection_ratio": round(rs / proj_bytes, 2),
        "num1_sum": merge_aggregates(parts1)["NUM1"]["sum"],
    }
    _log(f"projected NUM1-only query: {proj}")

    result = {
        "metric": f"exp3_device_aggregate_{agg.decoder.backend}",
        "platform": platform,
        "backend": agg.decoder.backend,
        "fused": agg.decoder.backend == "pallas",
        "end_to_end_MBps": round(total_mb / e2e, 1),
        "vs_baseline": round(total_mb / e2e / BASELINE_MBPS, 1),
        "h2d_MBps": round(c_bytes / (1024 * 1024) / h2d_s, 1),
        "device_compute_MBps": round(c_bytes / (1024 * 1024) / comp_s, 1),
        "host_pack_MBps": round(total_mb / pack_s, 1),
        "d2h_bytes": d2h_bytes,
        "records": int(sum(p["NUM1"]["count"] for p in parts) / 2000),
        "total_MB": round(total_mb, 1),
        "block_records": block,
        "projected_num1": proj,
        "trace": trace_dir,
    }
    _log(f"device query: {result}")
    _log(f"aggregate sample: NUM1 sum={merged['NUM1']['sum']:.0f} "
         f"count={merged['NUM1']['count']}")
    return result


def run_device_pipeline(mb_target: float, platform: str) -> dict:
    """The on-HBM end-to-end pipeline: ONE H2D transfer of the raw exp3
    file image, then frame (pointer-doubling RDW scan) -> select wide
    records -> pack -> fused decode -> aggregate, all inside device
    programs — zero host round trips until the scalar fetch. Reports the
    h2d / device-compute split so the link's rate and the chip's own
    throughput are never conflated."""
    import jax

    from cobrix_tpu.ops.device_framing import build_wide_pipeline
    from cobrix_tpu.parallel import DeviceAggregator
    from cobrix_tpu.reader.parameters import (
        MultisegmentParameters,
        ReaderParameters,
    )
    from cobrix_tpu.reader.var_len_reader import VarLenReader
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK, generate_exp3

    params = ReaderParameters(
        is_record_sequence=True,
        multisegment=MultisegmentParameters(
            segment_id_field="SEGMENT-ID",
            segment_id_redefine_map={"C": "STATIC_DETAILS",
                                     "P": "CONTACTS"}))
    reader = VarLenReader(EXP3_COPYBOOK, params)
    agg = DeviceAggregator(reader.copybook, columns=["NUM1", "NUM2"],
                           active_segment="STATIC_DETAILS")

    est_per_record = 16072 * 0.33 + 68 * 0.67
    n_records = max(64, int(mb_target * 1024 * 1024 / est_per_record))
    raw = generate_exp3(n_records, seed=100)
    buf = np.frombuffer(raw, dtype=np.uint8)
    total_mb = buf.nbytes / (1024 * 1024)
    # static wide-record bound: wide records dominate the bytes
    cap = -(-int(buf.nbytes / 16072 * 1.25 + 8) // 256) * 256
    cols = agg.gather_index  # byte projection when the query is sparse
    fn = build_wide_pipeline(agg.record_extent, cap=cap, columns=cols)

    t0 = time.perf_counter()
    x = jax.device_put(buf)
    x.block_until_ready()
    h2d_s = time.perf_counter() - t0

    # warmup: compile the framing pipeline + the aggregate program (the
    # device count scalar flows into submit unsynced — zero host round
    # trips between framing and aggregate)
    t0 = time.perf_counter()
    packed, count = fn(x)
    agg.fetch(agg.submit(packed, count))
    _log(f"device pipeline warmup (incl. compile): "
         f"{time.perf_counter() - t0:.1f}s; cap={cap}")

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        packed, count = fn(x)
        res = agg.fetch(agg.submit(packed, count))
        times.append(time.perf_counter() - t0)
    compute_s = min(times)
    result = {
        "metric": f"exp3_onhbm_pipeline_{agg.decoder.backend}",
        "platform": platform,
        "backend": agg.decoder.backend,
        "fused": agg.decoder.backend == "pallas",
        "total_MB": round(total_mb, 1),
        "h2d_MBps": round(total_mb / h2d_s, 1),
        "device_pipeline_MBps": round(total_mb / compute_s, 1),
        "end_to_end_MBps": round(total_mb / (h2d_s + compute_s), 1),
        "wide_records": int(res["NUM1"]["count"] / 2000),
        "num1_sum": res["NUM1"]["sum"],
    }
    _log(f"device on-HBM pipeline: {result}")
    return result


def run_exp1_device_stats(mb_target: float, platform: str) -> dict:
    """Fused device compute on the heterogeneous exp1 profile (195 fields,
    irregular offsets): decode + per-codec validity reduction entirely on
    device, timed on a device-resident batch so the number is the chip's
    decode throughput, not the link's."""
    from cobrix_tpu import parse_copybook
    from cobrix_tpu.parallel import ShardedColumnarDecoder
    from cobrix_tpu.testing.generators import EXP1_COPYBOOK, generate_exp1

    cb = parse_copybook(EXP1_COPYBOOK)
    dec = ShardedColumnarDecoder(cb)  # backend auto: pallas on TPU
    n_records = max(256, int(mb_target * 1024 * 1024) // 1493)
    data = generate_exp1(n_records, seed=100)
    mb = data.nbytes / (1024 * 1024)

    t0 = time.perf_counter()
    dec.decode_stats(data)  # compiles; includes the H2D
    _log(f"exp1 device stats warmup (incl. compile): "
         f"{time.perf_counter() - t0:.1f}s; backend={dec.backend}")

    x, n = dec.put(data)  # device-resident: time the chip, not the link
    x.block_until_ready()
    times = []
    out = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = dec.decode_stats(x, n)
        times.append(time.perf_counter() - t0)
    result = {
        "metric": f"exp1_device_stats_{dec.backend}",
        "platform": platform,
        "backend": dec.backend,
        "fused": dec.backend == "pallas",
        "total_MB": round(mb, 1),
        "device_compute_MBps": round(mb / min(times), 1),
        "records_per_s": int(n / min(times)),
        "valid_values": int(out["valid_values"]),
    }
    _log(f"exp1 device stats: {result}")
    return result


def run(backend: str, mb_target: float) -> dict:
    from cobrix_tpu.reader.parameters import (
        MultisegmentParameters,
        ReaderParameters,
    )
    from cobrix_tpu.reader.var_len_reader import VarLenReader
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK, generate_exp3

    # same reader configuration as the reference exp3 run (SparkCobolApp
    # with redefine-segment-id-map): the copybook is parsed with
    # STATIC-DETAILS / CONTACTS marked as segment redefines
    params = ReaderParameters(
        is_record_sequence=True,
        multisegment=MultisegmentParameters(
            segment_id_field="SEGMENT-ID",
            segment_id_redefine_map={"C": "STATIC_DETAILS", "P": "CONTACTS"}))
    reader = VarLenReader(EXP3_COPYBOOK, params)

    # ~1/3 of records are 16 KB 'C' segments, the rest 64-byte contacts
    est_per_record = 16072 * 0.33 + 68 * 0.67
    n_records = max(64, int(mb_target * 1024 * 1024 / est_per_record))
    t0 = time.perf_counter()
    raw = generate_exp3(n_records, seed=100)
    _log(f"generated {len(raw) / 1e6:.1f} MB, {n_records} records "
         f"in {time.perf_counter() - t0:.1f}s")

    from cobrix_tpu import native

    total_mb = len(raw) / (1024 * 1024)
    _log(f"native framing: {native.available()}")

    def decode_all():
        # native RDW scan (VRLRecordReader loop in C++) + in-place decode
        # of numeric groups from the file image (decode_raw skips the
        # wide-record pack copy; only the narrow string prefix is packed)
        offsets, lengths = native.rdw_scan(raw, big_endian=False)
        out = []
        for seg_len in np.unique(lengths):
            # segment discrimination by record length (C records carry the
            # 2000-element strategy block; P contacts are 60 bytes)
            pos = np.nonzero(lengths == seg_len)[0]
            active = "CONTACTS" if seg_len < 1000 else "STATIC_DETAILS"
            dec = reader._decoder_for_segment(active, backend)
            d = dec.decode_raw(raw, offsets[pos], lengths[pos])
            # decode_raw DEFERS numeric and string groups as lazy
            # markers (the Arrow path emits them straight into Arrow
            # buffers); a decode-only number must force every plane to
            # actually materialize or it times pointer shuffling and
            # the e2e/decode-only ratio denominator is fiction
            d.materialize_numeric_all()
            for col, col_out in list(d._out.items()):
                if "lazy_string" in col_out:
                    d.column_arrays(col)
            out.append(d)
        return out

    # warmup (jit compile; excluded from timing)
    t0 = time.perf_counter()
    decode_all()
    _log(f"warmup (incl. compile): {time.perf_counter() - t0:.1f}s")

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        decoded = decode_all()
        times.append(time.perf_counter() - t0)
    best = min(times)
    n_rows = sum(d.n_records for d in decoded)
    mbps = total_mb / best
    _log(f"runs: {[f'{t:.2f}s' for t in times]}; {n_rows} records; "
         f"{mbps:.1f} MB/s; {n_rows / best:.0f} rec/s")
    return {
        "metric": f"exp3_multiseg_wide_decode_{backend}",
        "value": round(mbps, 2),
        "unit": "MB/s",
        "vs_baseline": round(mbps / BASELINE_MBPS, 2),
        "roofline": _roofline_field(mbps),
    }


def _assert_native_assembly_parity(kw: dict) -> bool:
    """In-run guard for the fused native assembly: a small exp3 sample
    read with native dispatch ON must be byte-identical to the
    pure-Python fallback. The diff itself is tools/asmcheck.py's
    check_profile (rows + tables + schema metadata + diagnostics
    ledgers) — ONE harness for bench, tests, and the smoke tool, so
    they cannot drift apart. A wrong-bytes fast path would RAISE the
    throughput numbers, so a mismatch must fail the bench, never ride
    along as data. Returns True when the native path was actually
    exercised (False = no .so, the numbers are pure-Python and the
    parity claim is vacuous)."""
    from cobrix_tpu import native
    from cobrix_tpu.testing.generators import generate_exp3

    if not native.available():
        return False
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import asmcheck

    asmcheck.check_profile("bench_exp3_parity",
                           generate_exp3(256, seed=100), kw)
    return True


def run_exp3_to_arrow(mb_target: float) -> dict:
    """exp3 multiseg-wide END-TO-END: file -> RDW framing -> segment
    split -> decode -> Arrow table, the same span the reference's
    8.0 MB/s covers (its job wrote Parquet columns, not raw decodes).
    Best of pipelined and sequential, like exp1/exp2. Native-vs-Python
    assembly parity is asserted in-run BEFORE any number is emitted."""
    import tempfile

    from cobrix_tpu.testing.generators import EXP3_COPYBOOK, generate_exp3

    est_per_record = 16072 * 0.33 + 68 * 0.67
    n_records = max(64, int(mb_target * 1024 * 1024 / est_per_record))
    raw = generate_exp3(n_records, seed=100)
    mb = len(raw) / (1024 * 1024)
    kw = dict(copybook_contents=EXP3_COPYBOOK, is_record_sequence="true",
              segment_field="SEGMENT-ID",
              redefine_segment_id_map="STATIC-DETAILS => C",
              redefine_segment_id_map_1="CONTACTS => P")
    # wrong bytes must fail the bench here, not pass it faster
    native_exercised = _assert_native_assembly_parity(kw)
    path = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
            f.write(raw)
            path = f.name
        # either variant alone carries the metric: one failing must not
        # drop the honest headline back to the decode-only comparison
        seq_best = pipe_best = None
        table = None
        try:
            seq_best, table, _ = _best_to_arrow(path, kw)
        except Exception as exc:
            _log(f"exp3 sequential to_arrow failed: {exc}")
        try:
            pipe_best, table, _ = _best_to_arrow(
                path, dict(kw, **_pipeline_kw()))
        except Exception as exc:
            _log(f"exp3 pipelined to_arrow failed: {exc}")
        top = _top_fields_profile(path, kw)
    finally:
        if path:
            os.unlink(path)
    if table is None:
        raise RuntimeError("both exp3 to_arrow variants failed")
    best = min(t for t in (seq_best, pipe_best) if t)
    mbps = mb / best
    result = {
        "metric": "exp3_multiseg_wide_to_arrow",
        "value": round(mbps, 2),
        "unit": "MB/s",
        "vs_baseline": round(mbps / BASELINE_MBPS, 2),
        "rows_per_s": int(table.num_rows / best),
        "pipelined_MBps": (round(mb / pipe_best, 1) if pipe_best else None),
        "sequential_MBps": (round(mb / seq_best, 1) if seq_best else None),
        "native_assembly": native_exercised,
        "roofline": _roofline_field(mbps),
        "top_fields": top,
    }
    _log(f"exp3 end-to-end to_arrow: {result}")
    return result


def run_exp_pushdown(mb_target: float) -> dict:
    """Query-pushdown end-to-end: the exp3 wide copybook read with
    `select` of 3 columns and a ~1%-selective COMPANY-ID filter,
    against the full decode of the same input. The value is the
    pushed-down read's effective MB/s (input bytes over wall time);
    `speedup` is the claim tools/benchgate.py gates (>= 3x, ISSUE 13
    acceptance): plan pruning must make the untouched columns actually
    free, and the pre-decode drop must keep pruned records away from
    the wide decode. Parity is asserted in-run: the pushed-down table
    must equal post-hoc filter+null-projection of the full table."""
    import tempfile

    import pyarrow.compute as pc

    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK, generate_exp3

    est_per_record = 16072 * 0.33 + 68 * 0.67
    n_records = max(256, int(mb_target * 1024 * 1024 / est_per_record))
    raw = generate_exp3(n_records, seed=100)
    mb = len(raw) / (1024 * 1024)
    kw = dict(copybook_contents=EXP3_COPYBOOK, is_record_sequence="true",
              segment_field="SEGMENT-ID",
              schema_retention_policy="collapse_root",
              redefine_segment_id_map="STATIC-DETAILS => C",
              redefine_segment_id_map_1="CONTACTS => P")

    def best_of(read_kw):
        """Best of sequential and pipelined, like run_exp3_to_arrow —
        a heavily-pruned scan finishes under the pipeline's scheduling
        tick, so sequential often wins it while pipelined wins the
        full decode."""
        best = None
        for variant in (read_kw, dict(read_kw, **_pipeline_kw())):
            try:
                t, table, metrics = _best_to_arrow(path, variant)
            except Exception as exc:
                _log(f"exp_pushdown variant failed: {exc}")
                continue
            if best is None or t < best[0]:
                best = (t, table, metrics)
        if best is None:
            raise RuntimeError("every exp_pushdown variant failed")
        return best

    path = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
            f.write(raw)
            path = f.name
        full_best, full_table, _ = best_of(kw)
        # a ~1%-selective predicate from the data itself: enough
        # distinct COMPANY-IDs to cover ~1% of records
        ids = full_table["COMPANY_ID"].to_pylist()
        import collections

        counts = collections.Counter(i for i in ids if i)
        target = max(1, len(ids) // 100)
        chosen, covered = [], 0
        for value, cnt in counts.most_common():
            if covered >= target:
                break
            chosen.append(value)
            covered += cnt
        filt = "COMPANY_ID in (%s)" % ", ".join(
            "'%s'" % v for v in chosen)
        select = "SEGMENT-ID,COMPANY-ID,COMPANY-NAME"
        push_kw = dict(kw, select=select, filter=filt)
        push_best, push_table, push_metrics = best_of(push_kw)
        # parity: pushed-down == post-hoc filter of the full table on
        # the selected columns, byte-identical
        mask = pc.fill_null(pc.is_in(
            full_table["COMPANY_ID"],
            value_set=__import__("pyarrow").array(chosen)), False)
        expect = full_table.filter(mask)
        sel_cols = ["SEGMENT_ID", "COMPANY_ID"]
        name_of = (lambda t: pc.struct_field(
            t["STATIC_DETAILS"], "COMPANY_NAME").combine_chunks())
        parity = (push_table.num_rows == expect.num_rows
                  and push_table.select(sel_cols).equals(
                      expect.select(sel_cols))
                  and name_of(push_table).equals(name_of(expect)))
        if not parity:
            # a wrong-rows pushdown would otherwise RAISE the speedup
            # (fewer rows decoded) and sail through the gate — parity
            # failure must fail the experiment, not ride along as data
            raise RuntimeError(
                f"exp_pushdown parity violation: pushed-down "
                f"{push_table.num_rows} rows vs post-hoc "
                f"{expect.num_rows}")
    finally:
        if path:
            os.unlink(path)
    full_mbps = mb / full_best
    push_mbps = mb / push_best
    pushdown = push_metrics.get("pushdown") or {}
    result = {
        "metric": "exp_pushdown_to_arrow",
        "value": round(push_mbps, 2),
        "unit": "MB/s",
        "full_MBps": round(full_mbps, 2),
        "speedup": round(push_mbps / full_mbps, 2),
        "rows_pruned": pushdown.get("records_pruned"),
        "bytes_skipped": pushdown.get("bytes_skipped"),
        "selectivity": pushdown.get("selectivity"),
        "parity": bool(parity),
        "roofline": _roofline_field(push_mbps),
    }
    _log(f"exp_pushdown: {result}")
    return result


def run_exp_stats(mb_target: float) -> dict:
    """Statistics chunk-skipping end-to-end: a key-sorted fixed-length
    input (disjoint per-chunk zone maps) profiled once with
    `collect_stats`, then a ~1-chunk-selective equality scan measured
    warm with `use_stats` against the SAME scan answered by PR 13's
    record-level pushdown alone. The value is the warm skipped scan's
    effective MB/s (input bytes over wall time); `speedup_vs_pushdown`
    is the claim tools/benchgate.py gates (>= 2x, ISSUE 19
    acceptance): dropping proven-no-match chunks BEFORE framing must
    beat framing + stage-1-deciding every record. Parity is asserted
    in-run (stats table == pushdown table, byte-identical), and the
    aggregate path is timed beside its decode ground truth."""
    import tempfile

    from cobrix_tpu import read_cobol
    from cobrix_tpu.query import dataset
    from cobrix_tpu.stats.aggregate import parse_specs

    copybook = """
       01  REC.
           05  KEY-ID    PIC 9(8).
           05  NAME      PIC X(8).
    """
    n = max(4096, int(mb_target * 1024 * 1024) // 16)
    raw = bytearray()
    for i in range(n):
        raw += bytes(0xF0 + int(d) for d in f"{i:08d}")
        raw += bytes((0xC1 + i % 3,)) * 8
    mb = len(raw) / (1024 * 1024)
    kw = dict(copybook_contents=copybook)
    flt = f"KEY_ID == {n // 2}"
    path = cache = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
            f.write(bytes(raw))
            path = f.name
        cache = tempfile.mkdtemp(prefix="bench_stats_")
        t0 = time.perf_counter()
        read_cobol(path, cache_dir=cache, collect_stats="true",
                   stats_chunk_mb="0.25", **kw)
        profile_build_s = time.perf_counter() - t0
        push_best, push_table, _ = _best_to_arrow(
            path, dict(kw, filter=flt))
        warm_kw = dict(kw, filter=flt, cache_dir=cache,
                       use_stats="true", stats_chunk_mb="0.25")
        warm_best, warm_table, warm_metrics = _best_to_arrow(
            path, warm_kw)
        if not warm_table.equals(push_table):
            # a wrong skip would RAISE the speedup (fewer chunks read)
            # and sail through the gate — parity failure must fail the
            # experiment, not ride along as data
            raise RuntimeError(
                f"exp_stats parity violation: skipped scan "
                f"{warm_table.num_rows} rows vs pushdown "
                f"{push_table.num_rows}")
        aggs = ["count", "min:KEY_ID", "max:KEY_ID", "sum:KEY_ID"]
        ds = dataset(path, cache_dir=cache, use_stats="true", **kw)
        t0 = time.perf_counter()
        fast = ds._aggregate_from_stats(parse_specs(aggs))
        agg_stats_ms = (time.perf_counter() - t0) * 1000
        t0 = time.perf_counter()
        plain = dataset(path, **kw).aggregate(aggs)
        agg_decode_ms = (time.perf_counter() - t0) * 1000
        if fast is None or fast != plain:
            raise RuntimeError(
                f"exp_stats aggregate divergence: {fast} != {plain}")
    finally:
        if path:
            os.unlink(path)
        if cache:
            import shutil

            shutil.rmtree(cache, ignore_errors=True)
    push_mbps = mb / push_best
    warm_mbps = mb / warm_best
    pushdown = warm_metrics.get("pushdown") or {}
    result = {
        "metric": "exp_stats_to_arrow",
        "value": round(warm_mbps, 2),
        "unit": "MB/s",
        "pushdown_MBps": round(push_mbps, 2),
        "speedup_vs_pushdown": round(warm_mbps / push_mbps, 2),
        "profile_build_s": round(profile_build_s, 3),
        "chunks_skipped": pushdown.get("chunks_skipped"),
        "chunks_considered": pushdown.get("chunks_considered"),
        "aggregate_from_stats_ms": round(agg_stats_ms, 2),
        "aggregate_decode_ms": round(agg_decode_ms, 2),
        "parity": True,
        "roofline": _roofline_field(warm_mbps),
    }
    _log(f"exp_stats: {result}")
    return result


def _headline(decode_only: dict, e2e: dict) -> dict:
    """Merge the two exp3 measurements into the emitted headline: the
    honest end-to-end number carries `value`/`vs_baseline`; the
    kernel-only number rides along as `decode_only`, and their ratio is
    emitted as `e2e_vs_decode_only` — the assembly-overhead metric
    tools/benchgate.py gates against an absolute floor (ROADMAP item 1:
    end-to-end trending toward decode-only). A failed e2e run falls
    back to the decode headline with the error recorded (and NO ratio,
    which the gate treats as a floor failure, not a free pass)."""
    if "value" not in e2e:
        out = dict(decode_only)
        out["to_arrow"] = e2e  # the error record — never silently lost
        return out
    out = dict(e2e)
    out["decode_only"] = decode_only
    dv = decode_only.get("value")
    if isinstance(dv, (int, float)) and dv > 0:
        out["e2e_vs_decode_only"] = round(e2e["value"] / dv, 4)
    # the HEADLINE line: the roofline fraction leads (the claim that
    # survives machine swaps — arxiv 2606.22423's throughput-law view),
    # the absolute MB/s and the assembly-overhead ratio follow
    roof = e2e.get("roofline") or {}
    frac = roof.get("fraction")
    _log("HEADLINE exp3 e2e: "
         + (f"{frac:.1%} of calibrated memory bandwidth "
            f"({roof.get('calibrated_GBps')} GB/s), "
            if frac is not None else "roofline uncalibrated, ")
         + f"{e2e['value']} MB/s, e2e/decode-only "
         + f"{out.get('e2e_vs_decode_only', 'n/a')}")
    return out


def _pipeline_kw() -> dict:
    """Pipeline knobs for the bench: auto worker count, chunks sized so
    the default 40MB inputs split ~10 ways (overridable via env)."""
    return dict(
        pipeline_workers=os.environ.get("BENCH_PIPELINE_WORKERS", "-1"),
        chunk_size_mb=os.environ.get("BENCH_CHUNK_MB", "8"))


def _best_to_arrow(path: str, kw: dict, runs: int = 3):
    """(best seconds, table, metrics dict) over `runs` timed reads."""
    from cobrix_tpu import read_cobol

    read_cobol(path, **kw).to_arrow()  # warmup
    times = []
    out = None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = read_cobol(path, **kw)
        table = out.to_arrow()
        times.append(time.perf_counter() - t0)
    return min(times), table, out.metrics.as_dict()


def run_exp1_side_metric(mb_target: float) -> dict:
    """exp1 fixed-length type-variety profile (195 fields / 1,493 B per
    record, data/test6_copybook.cob layout): the string/DISPLAY-heaviest
    baseline workload. Reference single-core: ~6.3 MB/s
    (performance/exp1_raw_records.csv). Timed end-to-end like the
    reference job: file -> record matrix -> kernels -> Arrow columns
    (decode alone would under-count now that string transcode is lazy).

    Headline value is the BEST of the pipelined and sequential
    configurations (both reported separately; `pipeline_on_vs_off`
    attributes the difference honestly — on few-core machines the
    pipeline's thread overhead can lose to the sequential OpenMP
    kernels), plus the per-stage busy breakdown so a pipeline win or
    regression is attributable (read/frame/decode/assemble + overlap)."""
    import tempfile

    from cobrix_tpu.testing.generators import EXP1_COPYBOOK, generate_exp1

    baseline = 6.3
    n_records = max(64, int(mb_target * 1024 * 1024) // 1493)
    t0 = time.perf_counter()
    data = generate_exp1(n_records, seed=100)
    mb = data.nbytes / (1024 * 1024)
    _log(f"exp1: generated {mb:.1f} MB, {n_records} records "
         f"in {time.perf_counter() - t0:.1f}s")
    path = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
            f.write(data.tobytes())
            path = f.name
        kw = dict(copybook_contents=EXP1_COPYBOOK)
        seq_best, _, _ = _best_to_arrow(path, kw)
        pipe_best, table, pipe_metrics = _best_to_arrow(
            path, dict(kw, **_pipeline_kw()))
        top = _top_fields_profile(path, dict(kw, **_pipeline_kw()))
    finally:
        if path:
            os.unlink(path)
    best = min(pipe_best, seq_best)  # headline: the faster configuration
    result = {
        "metric": "exp1_fixed_length_to_arrow",
        "value": round(mb / best, 1),
        "unit": "MB/s",
        "vs_baseline": round(mb / best / baseline, 1),
        "records_per_s": int(table.num_rows / best),
        "pipelined_MBps": round(mb / pipe_best, 1),
        "sequential_MBps": round(mb / seq_best, 1),
        "pipeline_on_vs_off": round(seq_best / pipe_best, 2),
        "roofline": _roofline_field(mb / best),
        "top_fields": top,
        # the read's FULL structured metrics (timings, stage busy,
        # pipeline overlap, plan_cache) so the perf trajectory carries
        # attributable stage breakdowns, not just headline MB/s
        "read_metrics": pipe_metrics,
    }
    _log(f"side metric exp1_fixed_length: {result}")
    return result


def run_exp2_side_metric(mb_target: float) -> dict:
    """exp2 narrow-record profile (64-68 B/rec): the FULL pipeline — file
    -> RDW framing -> segment split -> decode -> Arrow table — not just
    the decode step. Uses the multi-host (process) scan when the machine
    has cores for it (parallel/hosts.py; cpu_count=1 runs single-process).
    Reference exp2 single-core baseline: ~9.4 MB/s (BASELINE.md)."""
    import tempfile

    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing.generators import EXP2_COPYBOOK, generate_exp2

    baseline = 9.4
    n_records = max(1000, int(mb_target * 1024 * 1024 / 66))
    raw = generate_exp2(n_records, seed=100)
    mb = len(raw) / (1024 * 1024)
    cores = os.cpu_count() or 1
    kw = dict(copybook_contents=EXP2_COPYBOOK, is_record_sequence="true",
              segment_field="SEGMENT-ID",
              redefine_segment_id_map="STATIC-DETAILS => C",
              redefine_segment_id_map_1="CONTACTS => P",
              segment_id_prefix="BENCH")
    if cores > 1:
        kw["hosts"] = str(min(cores, 16))
        kw["input_split_size_mb"] = str(
            max(4, int(mb / (2 * min(cores, 16)))))
    path = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat",
                                         delete=False) as f:
            f.write(raw)
            path = f.name
        def best_of_3(options):
            read_cobol(path, **options).to_arrow()  # warmup
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                tbl = read_cobol(path, **options).to_arrow()
                times.append(time.perf_counter() - t0)
            return min(times), tbl

        best, table = best_of_3(kw)
        # the reference's exp2 app also generates Seg_Id0/Seg_Id1
        # (SparkCobolApp); measure that configuration too — its failure
        # must not discard the base metric
        with_ids = None
        try:
            with_ids, _ = best_of_3(
                dict(kw, segment_id_level0="C", segment_id_level1="P"))
        except Exception as exc:
            _log(f"exp2 seg-id variant failed: {exc}")
        # pipeline on/off, single-process (hosts stripped): attributes the
        # thread-pipeline win separately from the process executor's
        pipe_on = pipe_off = None
        pipe_metrics = None
        base_kw = {k: v for k, v in kw.items()
                   if k not in ("hosts", "input_split_size_mb")}
        try:
            pipe_off, _ = best_of_3(base_kw)
            pipe_on, _, pipe_metrics = _best_to_arrow(
                path, dict(base_kw, **_pipeline_kw()))
        except Exception as exc:
            _log(f"exp2 pipeline variant failed: {exc}")
        top = _top_fields_profile(path, base_kw)
    finally:
        if path:
            os.unlink(path)
    result = {
        "metric": "exp2_multiseg_narrow_to_arrow",
        "value": round(mb / best, 1),
        "unit": "MB/s",
        "vs_baseline": round(mb / best / baseline, 1),
        "roofline": _roofline_field(mb / best),
        "top_fields": top,
        "with_seg_ids_MBps": (round(mb / with_ids, 1)
                              if with_ids else None),
        "rows_per_s": int(table.num_rows / best),
        "hosts": int(kw.get("hosts", 1)),
        "pipelined_MBps": (round(mb / pipe_on, 1) if pipe_on else None),
        "sequential_MBps": (round(mb / pipe_off, 1) if pipe_off else None),
        "pipeline_on_vs_off": (round(pipe_off / pipe_on, 2)
                               if pipe_on and pipe_off else None),
        "read_metrics": pipe_metrics,
    }
    _log(f"side metric exp2_multiseg_narrow: {result} "
         f"(baseline {baseline} MB/s)")
    return result


def _device_metrics(mb_target: float, platform: str) -> dict:
    """Every device-path measurement: the query (decode+aggregate, blocks
    streamed over the link), the on-HBM framing pipeline, and the exp1
    fused device-stats compute number. A leg that raises fails the run:
    a device number that is missing is not a slower number."""
    dev_mb = min(mb_target, float(os.environ.get("BENCH_DEVICE_MB", "64")))
    return {
        "device_query": run_device_query(dev_mb, platform),
        "device_pipeline": run_device_pipeline(min(dev_mb, 32.0), platform),
        "exp1_device_stats": run_exp1_device_stats(min(dev_mb, 16.0),
                                                   platform),
    }


def _init_device() -> dict:
    """Initialise JAX in this process, once, and say what it found. The
    bench measures a TPU; BENCH_FORCE_CPU is the only way to run it
    elsewhere, and every figure of such a run is labelled `cpu`."""
    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _log(f"device: {device}")
    if dev.platform != "tpu" and not os.environ.get("BENCH_FORCE_CPU"):
        raise SystemExit(
            f"bench.py measures a TPU and found {device}; set "
            "BENCH_FORCE_CPU=1 to run its device legs on the CPU, "
            "labelled as such")
    return device


def main():
    mb_target = float(os.environ.get("BENCH_MB", "64"))
    backend = os.environ.get("BENCH_BACKEND", "numpy")
    device = _init_device()
    # anchor every experiment against the machine's memory bandwidth
    # (one-time; cached across rounds) BEFORE any timing runs
    _calibrate_roofline()

    # the device-resident measurements (the decoded columns never cross
    # the link; scalars do)
    device_legs = _device_metrics(mb_target, device["platform"])

    side = _side_metrics(mb_target)
    result = run(backend, mb_target)
    # the headline: end-to-end Arrow conversion of the same workload (the
    # decode-only number overstates vs the full-conversion baseline)
    try:
        e2e = run_exp3_to_arrow(mb_target)
    except Exception as exc:
        _log(f"exp3 to_arrow timing failed: {exc}")
        e2e = {"metric": "exp3_multiseg_wide_to_arrow",
               "error": str(exc)[:400]}
    result = _headline(result, e2e)
    _emit(result, device, device_legs, side)


def _emit(result: dict, device: dict, device_legs: dict,
          side_metrics: dict):
    result = dict(result)
    result["device"] = device
    result.update(device_legs)
    result.update(side_metrics)
    print(json.dumps(result), flush=True)


def run_hierarchical_side_metric(mb_target: float) -> dict:
    """Hierarchical (IMS-style) 7-segment profile through the span-based
    columnar Arrow assembly (TestDataGen17Hierarchical layout). No
    reference CSV exists for this shape — reported informationally."""
    import tempfile

    from cobrix_tpu import read_cobol
    from cobrix_tpu.reader.hierarchical_arrow import assembly_stats
    from cobrix_tpu.testing import generators as g

    assembly_stats(reset=True)
    n_companies = max(50, int(mb_target * 1024 * 1024 / 1350))
    raw = g.generate_hierarchical(n_companies, seed=100)
    mb = len(raw) / (1024 * 1024)
    seg_opts = {f"redefine_segment_id_map:{i}": f"{name} => {sid}"
                for i, (sid, name) in enumerate(
                    g.HIERARCHICAL_SEGMENT_MAP.items())}
    child_opts = {f"segment-children:{i}": f"{parent} => {child}"
                  for i, (child, parent) in enumerate(
                      g.HIERARCHICAL_PARENT_MAP.items())}
    kw = dict(copybook_contents=g.HIERARCHICAL_COPYBOOK,
              is_record_sequence="true", segment_field="SEGMENT-ID",
              **seg_opts, **child_opts)
    path = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
            f.write(raw)
            path = f.name
        table = read_cobol(path, **kw).to_arrow()  # warmup
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            table = read_cobol(path, **kw).to_arrow()
            times.append(time.perf_counter() - t0)
    finally:
        if path:
            os.unlink(path)
    stats = assembly_stats(reset=True)
    result = {
        "metric": "hierarchical_7seg_to_arrow",
        "value": round(mb / min(times), 1),
        "unit": "MB/s",
        "vs_exp3_bar": round(mb / min(times) / 160.0, 2),  # 20x exp3 bar
        "roots_per_s": int(table.num_rows / min(times)),
        "roofline": _roofline_field(mb / min(times)),
        "assembly": stats,  # columnar builds vs row-path bails
    }
    _log(f"side metric hierarchical: {result}")
    return result


def run_serve_side_metric(mb_target: float) -> dict:
    """exp_serve: the streaming serving tier (cobrix_tpu.serve) vs the
    in-process read, same exp1 input. Two numbers matter: streamed
    end-to-end MB/s (decode + Arrow IPC framing + TCP loopback + client
    reassembly — the tax a serving client pays over `to_arrow()`), and
    time-to-first-batch, which must land BELOW the one-shot latency:
    that gap is the whole point of streaming delivery (a client renders
    after one chunk decodes, not after the whole table exists)."""
    import tempfile

    from cobrix_tpu.serve import ScanServer, stream_scan
    from cobrix_tpu.testing.generators import EXP1_COPYBOOK, generate_exp1

    n_records = max(64, int(mb_target * 1024 * 1024) // 1493)
    data = generate_exp1(n_records, seed=100)
    mb = data.nbytes / (1024 * 1024)
    path = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
            f.write(data.tobytes())
            path = f.name
        # both sides run the SAME pipelined config, chunked ~8 ways:
        # streaming only wins first-batch latency when the scan has
        # several chunks to deliver incrementally, and the one-shot
        # reference must not differ in anything but delivery
        kw = dict(copybook_contents=EXP1_COPYBOOK,
                  pipeline_workers=os.environ.get(
                      "BENCH_PIPELINE_WORKERS", "-1"),
                  chunk_size_mb=os.environ.get(
                      "BENCH_SERVE_CHUNK_MB", str(max(1, round(mb / 8)))))
        # in-process reference; its warmup also warms the compile caches
        # the server shares, so neither side pays the parse
        one_shot_s, table, _ = _best_to_arrow(path, kw)
        srv = ScanServer(enable_http=False).start()
        errors = []
        try:
            # rows/batches come from the best-total run so throughput
            # fields all describe ONE run; first-batch is best-of-runs
            # like every other latency in this file
            best = None  # (total, rows, batches)
            best_first = None
            for _ in range(3):
                t0 = time.perf_counter()
                first = None
                rows = batches = 0
                with stream_scan(srv.address, path, tenant="bench",
                                 **kw) as stream:
                    for batch in stream:
                        if first is None:
                            first = time.perf_counter() - t0
                        rows += batch.num_rows
                        batches += 1
                total = time.perf_counter() - t0
                if rows != table.num_rows:
                    errors.append(f"streamed {rows} rows != in-process "
                                  f"{table.num_rows}")
                if best is None or total < best[0]:
                    best = (total, rows, batches)
                if first is not None and (best_first is None
                                          or first < best_first):
                    best_first = first
        finally:
            srv.stop()
    finally:
        if path:
            os.unlink(path)
    best_total, rows, batches = best
    if best_first is None:
        best_first = best_total
    result = {
        "metric": "exp_serve_streamed_to_arrow",
        "value": round(mb / best_total, 1),
        "unit": "MB/s",
        "roofline": _roofline_field(mb / best_total),
        "rows": rows,
        "batches": batches,
        "one_shot_s": round(one_shot_s, 4),
        "stream_total_s": round(best_total, 4),
        "stream_vs_in_process": round(one_shot_s / best_total, 2),
        "first_batch_s": round(best_first, 4),
        # >1.0 = the stream's first batch beat the whole one-shot read
        # (the acceptance bar; asserted hard in tools/servecheck.py)
        "first_batch_speedup": round(one_shot_s / best_first, 2),
    }
    if best_first >= one_shot_s:
        errors.append(f"first batch at {best_first:.3f}s did NOT beat "
                      f"the {one_shot_s:.3f}s one-shot read")
    if errors:  # every failure survives into the JSON, none overwritten
        result["error"] = "; ".join(errors)
    _log(f"side metric exp_serve: {result}")
    return result


def run_serve_fleet_metric(mb_target: float) -> dict:
    """exp_serve fleet mode: aggregate routed throughput as the fleet
    scales N=1 -> 2 -> 4 replicas behind the routing front. Four files
    spread across the fleet by cache affinity (each file's scans pin to
    the replica whose caches are warm for it), so the aggregate MB/s of
    a concurrent scan mix should GROW with N while the warm-affinity
    hit rate stays high — that pair is the scaling claim PR 16's router
    exists to earn. Served from ``memory://`` so the io cache planes
    (and the peer tier's wire path) engage exactly as they would
    against object storage."""
    import shutil
    import tempfile
    import threading

    import fsspec

    from cobrix_tpu.fleet.router import RoutingFront, route_scan
    from cobrix_tpu.serve import ScanServer
    from cobrix_tpu.testing.generators import EXP1_COPYBOOK, generate_exp1

    n_files = 4
    per_file = max(64, int(mb_target * 1024 * 1024 / n_files) // 1493)
    fs = fsspec.filesystem("memory")
    paths = []
    for i in range(n_files):
        data = generate_exp1(per_file, seed=200 + i)
        with fs.open(f"/bench-fleet/f{i}.dat", "wb") as f:
            f.write(data.tobytes())
        paths.append((f"memory://bench-fleet/f{i}.dat",
                      data.nbytes / (1024 * 1024)))
    total_mb = sum(mb for _, mb in paths)
    kw = dict(copybook_contents=EXP1_COPYBOOK)
    hb_s = 0.2
    work = tempfile.mkdtemp(prefix="bench-fleet-")
    errors = []
    per_n = {}
    try:
        for n in (1, 2, 4):
            fleet_dir = os.path.join(work, f"fleet-{n}")
            servers = [
                ScanServer(
                    port=0, enable_http=False,
                    server_options={"cache_dir": os.path.join(
                        work, f"cache-{n}-{i}")},
                    fleet=True, replica_id=f"bench-{n}-{i}",
                    heartbeat_interval_s=hb_s,
                    fleet_dir=fleet_dir).start()
                for i in range(n)]
            front = RoutingFront(fleet_dir, slo_aware=False)
            try:
                deadline = time.monotonic() + 15
                while (len(front.registry.read()) < n
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                # warm pass: every file scanned once (caches + heat)
                for path, _mb in paths:
                    route_scan(front, path, tenant="bench",
                               **kw).table()
                time.sleep(hb_s * 2)  # heat rides the next heartbeat
                base = front.state()
                threads, rows = [], []

                def one(path):
                    t = route_scan(front, path, tenant="bench",
                                   **kw).table()
                    rows.append(t.num_rows)

                for _round in range(2):
                    for path, _mb in paths:
                        threads.append(threading.Thread(
                            target=one, args=(path,)))
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=300)
                wall = time.perf_counter() - t0
                if len(rows) != len(threads) \
                        or sum(rows) != per_file * len(threads):
                    errors.append(f"N={n}: row mismatch {sum(rows)}")
                st = front.state()
                decisions = st["decisions"] - base["decisions"]
                hits = st["affinity_hits"] - base["affinity_hits"]
                per_n[str(n)] = {
                    "aggregate_MBps": round(total_mb * 2 / wall, 1),
                    "affinity_hit_rate": round(
                        hits / max(1, decisions), 2),
                    "routed": st["routed"],
                }
            finally:
                for srv in servers:
                    srv.stop()
    finally:
        shutil.rmtree(work, ignore_errors=True)
        try:
            fs.rm("/bench-fleet", recursive=True)
        except Exception:
            pass
    agg4 = per_n.get("4", {}).get("aggregate_MBps", 0.0)
    agg1 = per_n.get("1", {}).get("aggregate_MBps", 0.0)
    result = {
        "metric": "exp_serve_fleet_aggregate",
        "value": agg4,
        "unit": "MB/s",
        "scaling_4x": round(agg4 / agg1, 2) if agg1 else None,
        "warm_affinity_hit_rate": per_n.get("4", {}).get(
            "affinity_hit_rate"),
        "per_n": per_n,
    }
    if errors:
        result["error"] = "; ".join(errors)
    _log(f"side metric exp_serve fleet: {result}")
    return result


def run_roundtrip_side_metric(mb_target: float) -> dict:
    """exp_roundtrip: the write half (cobrix_tpu.encode) measured beside
    the read half it must mirror. Three numbers: encode MB/s (the
    vectorized BatchEncoder streaming a >=1M-record synthetic TXN
    corpus to disk, testing/corpus.py), decode MB/s of that same corpus
    end to end (read_cobol -> Arrow, the exp3-style e2e view of
    encoder-built data), and `roundtrip_parity` — decode->re-encode
    byte equality on a sample file, which tools/benchgate.py gates as a
    HARD failure with no history needed: fast encode of wrong bytes is
    worthless."""
    import shutil
    import tempfile

    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing import corpus

    n_records = max(1_000_000, int(mb_target * 1024 * 1024) // 35)
    tmpdir = tempfile.mkdtemp(prefix="bench_rt_")
    path = os.path.join(tmpdir, "txn.dat")
    try:
        t0 = time.perf_counter()
        info = corpus.write_fixed_corpus(path, n_records, seed=100)
        encode_s = time.perf_counter() - t0
        mb = info["bytes"] / (1024 * 1024)
        times = []
        rows = 0
        for _ in range(2):
            t0 = time.perf_counter()
            table = read_cobol(path,
                               **corpus.fixed_read_options()).to_arrow()
            times.append(time.perf_counter() - t0)
            rows = table.num_rows
        # parity: a separate small corpus re-encoded byte-for-byte (the
        # record-at-a-time write path; full-corpus parity is rtcheck's
        # job, here it is a cheap in-run guard)
        sample = 20_000
        spath = os.path.join(tmpdir, "sample.dat")
        corpus.write_fixed_corpus(spath, sample, seed=100)
        with open(spath, "rb") as f:
            sample_bytes = f.read()
        out = read_cobol(spath, **corpus.fixed_read_options())
        parity = out.to_ebcdic(framing="fixed") == sample_bytes
        result = {
            "metric": "exp_roundtrip_encode",
            "value": round(mb / encode_s, 1),
            "unit": "MB/s",
            "records": rows,
            "mb": round(mb, 1),
            "decode_mbps": round(mb / min(times), 1),
            "roundtrip_parity": bool(parity),
            "parity_sample_records": sample,
        }
        _log(f"side metric exp_roundtrip: {result}")
        return result
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def run_compressed_side_metric(mb_target: float) -> dict:
    """exp_compressed: the streaming decompression plane measured end
    to end. Two gzip feeds of the SAME synthetic TXN corpus at
    different compression ratios — the corpus writer's member-per-chunk
    level-1 stream (restartable, the production shape) and a solid
    level-9 single member — decode through read_cobol with a cache_dir.
    The headline is cold member-feed e2e MB/s of DECOMPRESSED bytes;
    `warm` re-scans the cache the cold pass populated (zero inflate
    work) as its own gated metric; `compressed_parity` asserts every
    leg byte-identical to the raw file's decode, which
    tools/benchgate.py gates as a HARD failure with no history needed:
    a fast inflate of wrong bytes is worthless."""
    import gzip as _gzip
    import shutil
    import tempfile

    from cobrix_tpu import read_cobol
    from cobrix_tpu.testing import corpus

    n_records = max(50_000, int(mb_target * 1024 * 1024) // 35)
    work = tempfile.mkdtemp(prefix="bench-comp-")
    try:
        raw = os.path.join(work, "txn.dat")
        chunk = max(1, n_records // 8)
        info = corpus.write_fixed_corpus(raw, n_records, seed=55,
                                         chunk_records=chunk)
        mb = info["bytes"] / (1024 * 1024)
        kw = corpus.fixed_read_options()
        base = read_cobol(raw, **kw).to_arrow()

        def matches(t) -> bool:
            return (t.num_rows == base.num_rows
                    and all(t.column(c).equals(base.column(c))
                            for c in base.column_names
                            if "File_Name" not in c))

        members = os.path.join(work, "txn.dat.gz")
        minfo = corpus.write_fixed_corpus(members, n_records, seed=55,
                                          chunk_records=chunk,
                                          compression="gzip")
        solid = os.path.join(work, "solid", "txn.dat.gz")
        os.makedirs(os.path.dirname(solid))
        with open(raw, "rb") as f:
            solid_wire = _gzip.compress(f.read(), compresslevel=9)
        with open(solid, "wb") as f:
            f.write(solid_wire)

        def timed(path, cache):
            t0 = time.perf_counter()
            out = read_cobol(path, cache_dir=cache,
                             compress_block_mb="2", **kw)
            table = out.to_arrow()
            return (time.perf_counter() - t0, table,
                    out.metrics.as_dict()["io"])

        parity = True
        cold_s, cold_table, _ = timed(members, os.path.join(work, "c1"))
        parity &= matches(cold_table)
        warm_times, warm_io = [], {}
        for _ in range(2):
            s, t, warm_io = timed(members, os.path.join(work, "c1"))
            parity &= matches(t)
            warm_times.append(s)
        solid_s, solid_table, _ = timed(solid, os.path.join(work, "c2"))
        parity &= matches(solid_table)
        warm_s = min(warm_times)
        result = {
            "metric": "exp_compressed_e2e",
            "value": round(mb / cold_s, 1),
            "unit": "MB/s",
            "roofline": _roofline_field(mb / cold_s),
            "mb": round(mb, 1),
            "records": base.num_rows,
            "ratio": round(info["bytes"] / minfo["wire_bytes"], 2),
            "solid_cold_MBps": round(mb / solid_s, 1),
            "solid_ratio": round(info["bytes"] / len(solid_wire), 2),
            "compressed_parity": bool(parity),
            "warm": {
                "metric": "exp_compressed_warm",
                "value": round(mb / warm_s, 1),
                "unit": "MB/s",
                "zero_inflate":
                    warm_io.get("decompressed_bytes_out", 0) == 0,
                "speedup_vs_cold": round(cold_s / warm_s, 2),
            },
        }
        _log(f"side metric exp_compressed: {result}")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def run_sink_side_metric(mb_target: float) -> dict:
    """exp_sink: the transactional lakehouse sink (cobrix_tpu.sink) vs
    bare streaming decode, same exp1 input tailed from a static file.
    Two numbers matter: sink end-to-end MB/s (tail + decode + Parquet
    serialization + staged write + fsync'd manifest commit + durable
    checkpoint ack per batch — the whole exactly-once protocol), and
    the overhead fraction vs a consumer that decodes the identical
    batches and throws them away: that gap is the price of the
    durability guarantee, and it should stay a modest multiple, not an
    order of magnitude."""
    import shutil
    import tempfile

    from cobrix_tpu.sink import read_dataset, sink_cobol
    from cobrix_tpu.streaming import tail_cobol
    from cobrix_tpu.testing.generators import EXP1_COPYBOOK, generate_exp1

    n_records = max(64, int(mb_target * 1024 * 1024) // 1493)
    data = generate_exp1(n_records, seed=77)
    mb = data.nbytes / (1024 * 1024)
    work = tempfile.mkdtemp(prefix="bench-sink-")
    errors = []
    try:
        path = os.path.join(work, "feed.dat")
        with open(path, "wb") as f:
            f.write(data.tobytes())
        # both sides pay exactly ONE idle_timeout_s wait by
        # construction (the tail drains the static file, then idles
        # once before finalize) — subtract that constant so MB/s
        # measures the work, not the poll clock
        idle_s = 0.2
        kw = dict(copybook_contents=EXP1_COPYBOOK,
                  poll_interval_s=0.02, idle_timeout_s=idle_s,
                  finalize_on_idle=True)

        def stream_only() -> float:
            t0 = time.perf_counter()
            rows = 0
            for batch in tail_cobol(path, **kw):
                rows += len(batch.to_arrow())
            if rows != n_records:
                errors.append(f"stream decoded {rows} rows "
                              f"!= {n_records}")
            return time.perf_counter() - t0 - idle_s

        def sink_run() -> float:
            ckpt = os.path.join(work, "ck")
            dataset = os.path.join(work, "dataset")
            for stale in (ckpt, dataset):
                shutil.rmtree(stale, ignore_errors=True)
            t0 = time.perf_counter()
            result = sink_cobol(
                tail_cobol(path, checkpoint_dir=ckpt, **kw), dataset)
            elapsed = time.perf_counter() - t0 - idle_s
            if result.records != n_records:
                errors.append(f"sink committed {result.records} rows "
                              f"!= {n_records}")
            if not read_dataset(dataset).num_rows == n_records:
                errors.append("sink read-back row count diverged")
            return elapsed

        stream_s = min(stream_only() for _ in range(2))
        sink_s = min(sink_run() for _ in range(2))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    result = {
        "metric": "exp_sink_e2e",
        "value": round(mb / sink_s, 1),
        "unit": "MB/s",
        "roofline": _roofline_field(mb / sink_s),
        "rows": n_records,
        "stream_decode_MBps": round(mb / stream_s, 1),
        "sink_total_s": round(sink_s, 4),
        "stream_total_s": round(stream_s, 4),
        # >1.0 = the durable commit protocol costs this factor over
        # decode-and-discard streaming of the same batches
        "sink_overhead_x": round(sink_s / stream_s, 2),
    }
    if errors:
        result["error"] = "; ".join(errors)
    _log(f"side metric exp_sink: {result}")
    return result


def _side_metrics(mb_target: float) -> dict:
    """exp1/exp2/hierarchical/serving profiles as named JSON fields; a
    side-metric failure must never break the headline bench."""
    side = {}
    try:
        side["exp1"] = run_exp1_side_metric(min(mb_target, 40.0))
    except Exception as exc:
        _log(f"exp1 side metric failed: {exc}")
    try:
        side["exp2"] = run_exp2_side_metric(min(mb_target, 40.0))
    except Exception as exc:
        _log(f"exp2 side metric failed: {exc}")
    try:
        side["hierarchical"] = run_hierarchical_side_metric(
            min(mb_target, 16.0))
    except Exception as exc:
        _log(f"hierarchical side metric failed: {exc}")
    try:
        side["exp_serve"] = run_serve_side_metric(min(mb_target, 24.0))
    except Exception as exc:
        _log(f"exp_serve side metric failed: {exc}")
    if isinstance(side.get("exp_serve"), dict):
        try:
            side["exp_serve"]["fleet"] = run_serve_fleet_metric(
                min(mb_target, 8.0))
        except Exception as exc:
            _log(f"exp_serve fleet metric failed: {exc}")
    try:
        side["exp_sink"] = run_sink_side_metric(min(mb_target, 16.0))
    except Exception as exc:
        _log(f"exp_sink side metric failed: {exc}")
    try:
        side["exp_pushdown"] = run_exp_pushdown(min(mb_target, 40.0))
    except Exception as exc:
        _log(f"exp_pushdown side metric failed: {exc}")
        side["exp_pushdown"] = {"metric": "exp_pushdown_to_arrow",
                                "error": str(exc)[:400]}
    try:
        side["exp_stats"] = run_exp_stats(min(mb_target, 24.0))
    except Exception as exc:
        _log(f"exp_stats side metric failed: {exc}")
        side["exp_stats"] = {"metric": "exp_stats_to_arrow",
                             "error": str(exc)[:400]}
    try:
        side["exp_roundtrip"] = run_roundtrip_side_metric(
            min(mb_target, 40.0))
    except Exception as exc:
        _log(f"exp_roundtrip side metric failed: {exc}")
        side["exp_roundtrip"] = {"metric": "exp_roundtrip_encode",
                                 "error": str(exc)[:400]}
    try:
        side["exp_compressed"] = run_compressed_side_metric(
            min(mb_target, 16.0))
    except Exception as exc:
        _log(f"exp_compressed side metric failed: {exc}")
        side["exp_compressed"] = {"metric": "exp_compressed_e2e",
                                  "error": str(exc)[:400]}
    return side


if __name__ == "__main__":
    main()
