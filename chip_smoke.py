#!/usr/bin/env python3
"""Run the device decode path end to end on the chip, once, and check it.

    python chip_smoke.py            # one chip: the four phases below
    python chip_smoke.py --chips 4  # four chips: the sharded phase only

One process holds the chip from start to end. The one-chip run drives the
public entry points at the commissioned sizes (upstream exp3 cut from
40 GB to 1 GiB and exp1 to 320 MiB, for the run's time limit):

  read_exp3         read_cobol(backend="pallas") of the exp3 file to Arrow
  read_exp1         the same for exp1, backend "pallas" and then "jax"
  device_aggregate  parallel.DeviceAggregator over the exp3 'C' records
  serve             three fetches from a serve.ScanServer in this process

Every result is compared, outside the timed part, with the host kernels
(the whole table) and with the scalar oracle (a seeded sample), and every
phase shows that the chip did the work: where the outputs lived, whether
the compiled program holds the Mosaic kernel, whether it was interpreted.
Each phase prints one JSON line. The last line of standard output is
{"ok": true, "device": {...}}, and it is printed only when JAX found the
TPU and every check held; anything else ends the run with a traceback
and a non-zero exit code.

The numbers printed are seconds and bytes of this one run, labelled with
the device. They are not a benchmark: nothing here is repeated or warmed
beyond the first compile.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

MIB = 1 << 20
EXP3_MIB = 1024          # upstream exp3 is 40 GB; cut for the time limit
EXP1_MIB = 256           # rounded up to whole read chunks: 320 MiB
# the warm-up inputs are prefixes of the real ones, long enough to reach
# the full device block (reader/columnar.DEVICE_BLOCK_BYTES) and so to
# compile the shape the real read launches
EXP3_WARM_MIB = 48
EXP1_WARM_MIB = 56
ORACLE_SAMPLE = 2000     # records re-decoded by the scalar oracle
SERVE_REQUESTS = 3
AGGREGATE_BLOCK = 2048   # 'C' records per device_aggregate launch
SHARDED_BATCH = 8192     # 'C' records of the --chips 4 sharded decode
# the repo's own bound for a device float64 sum against the host's
# (__graft_entry__.dryrun_multichip); counts, minima and maxima are exact
SUM_RTOL = 1e-6

EXP3_OPTIONS = {
    "is_record_sequence": "true",
    "segment_field": "SEGMENT-ID",
    "redefine_segment_id_map": "STATIC-DETAILS => C",
    "redefine_segment_id_map_1": "CONTACTS => P",
}


def check(ok, message: str) -> None:
    # not `assert`: the smoke must fail under `python -O` too
    if not ok:
        raise RuntimeError(f"chip_smoke: {message}")


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------- set-up

def build_native() -> None:
    """Build _libframing.so from the committed sources, in a child that
    never imports JAX (this process has not touched it yet either, so no
    one holds the chip), and refuse the numpy fallback: framing and pack
    ahead of the chip are the native ones in production."""
    subprocess.run(
        [sys.executable, "-W", "ignore::RuntimeWarning", "-m",
         "cobrix_tpu.native.build", "--force"],
        cwd=REPO, check=True, stdout=subprocess.DEVNULL)
    from cobrix_tpu import native

    check(native.available(), "native library did not load after a "
          "build from source")
    say(phase="setup", native_available=True)


def find_device(platform: str, count: int) -> dict:
    """Initialise JAX, once, and require `count` devices of `platform`."""
    import jax

    from cobrix_tpu.ops.device import ensure_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    check(device["platform"] == platform and device["count"] == count,
          f"need {count} {platform} device(s), JAX found {device}")
    say(phase="setup", device=device,
        compile_cache_dir=ensure_compile_cache(),
        JAX_COMPILATION_CACHE_DIR=os.environ.get(
            "JAX_COMPILATION_CACHE_DIR"))
    return device


def write_exp3(path: str, target_bytes: int, seed: int) -> int:
    """The exp3 profile (TestDataGen4CompaniesWide) onto disk, in chunks
    so the generator's memory stays bounded. Returns the file size."""
    from cobrix_tpu.testing.generators import generate_exp3

    written = 0
    chunk = 0
    with open(path, "wb") as f:
        while written < target_bytes:
            # one record in three is a 16,068 B 'C', the rest 64 B 'P'
            n = int(min(64 * MIB, target_bytes - written) / 5400) + 8
            written += f.write(generate_exp3(n, seed=seed + chunk))
            chunk += 1
    return written


def write_exp1(path: str, target_bytes: int, seed: int) -> int:
    """The exp1 profile (TestDataGen6TypeVariety) onto disk. A file of
    more than one read chunk is rounded up to whole chunks: a ragged
    last chunk falls into a smaller batch bucket, and exp1's program
    takes over a minute to compile for each bucket (ROADMAP A9)."""
    from cobrix_tpu.api import FIXED_READ_CHUNK_BYTES
    from cobrix_tpu.testing.generators import (EXP1_RECORD_SIZE,
                                               generate_exp1)

    records = -(-target_bytes // EXP1_RECORD_SIZE)
    per_chunk = FIXED_READ_CHUNK_BYTES // EXP1_RECORD_SIZE
    if records > per_chunk:
        records = -(-records // per_chunk) * per_chunk
    chunk = 0
    with open(path, "wb") as f:
        while records > 0:
            n = min(records, 44000)
            f.write(generate_exp1(n, seed=seed + chunk).tobytes())
            records -= n
            chunk += 1
    return os.path.getsize(path)


# ------------------------------------------------------- proof of device

def check_program(what: str, has_kernel, interpreted) -> None:
    """A Pallas program is what the platform promises: on a TPU the
    Mosaic kernel, never the interpreter; elsewhere (the CPU rehearsal)
    the interpreter, and no kernel."""
    import jax

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    check(has_kernel is on_tpu and interpreted is (not on_tpu),
          f"{what}: tpu_custom_call in the compiled program is "
          f"{has_kernel}, interpreted={interpreted}, on {platform}")


def check_device_use(stats, backend: str) -> None:
    """`stats` is a read's ReadMetrics device record (as_dict form): the
    read launched on the device, its outputs lived on JAX's first device,
    and the program is the one the backend names."""
    import jax

    check(stats and stats["launches"], f"{backend}: no device launch")
    first = jax.devices()[0]
    check(stats["devices"] == [str(first)],
          f"{backend}: outputs lived on {stats['devices']}, "
          f"expected [{first}]")
    if backend == "pallas":
        check_program(backend, stats["has_kernel"], stats["interpreted"])
    else:
        check(stats["has_kernel"] is False and stats["interpreted"] is None,
              f"{backend}: unexpected Pallas kernel in the program")


# ------------------------------------------------------------ read phases

def sample_indices(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=min(ORACLE_SAMPLE, n), replace=False))


def sample_rdw_file(path: str, out_path: str, seed: int) -> np.ndarray:
    """Copy a seeded sample of whole RDW records into `out_path`; returns
    their record indices."""
    from cobrix_tpu import native

    raw = np.memmap(path, dtype=np.uint8, mode="r")
    offsets, lengths = native.rdw_scan(raw, big_endian=False)
    idx = sample_indices(len(offsets), seed)
    with open(out_path, "wb") as f:
        for i in idx:
            # the 4-byte RDW header precedes the payload
            f.write(raw[offsets[i] - 4:offsets[i] + lengths[i]].tobytes())
    return idx


def sample_fixed_file(path: str, out_path: str, record_size: int,
                      seed: int) -> np.ndarray:
    raw = np.memmap(path, dtype=np.uint8, mode="r").reshape(-1, record_size)
    idx = sample_indices(raw.shape[0], seed)
    with open(out_path, "wb") as f:
        f.write(raw[idx].tobytes())
    return idx


def phase_read(name: str, device: dict, path: str, warm_path: str,
               options: dict, backends, sample, seed: int) -> dict:
    """Read `path` through read_cobol on each device backend and hold the
    tables to the host kernels (whole) and the scalar oracle (sample).
    Returns ({backend: table}, the host kernels' table)."""
    import pyarrow as pa

    from cobrix_tpu import read_cobol

    tables = {}
    for backend in backends:
        # the prefix compiles the shape; the real read should compile none
        t0 = time.perf_counter()
        warm = read_cobol(warm_path, backend=backend, **options)
        warm.to_arrow()
        warm_s = time.perf_counter() - t0
        warm_stats = warm.metrics.device_stats.as_dict()

        t0 = time.perf_counter()
        data = read_cobol(path, backend=backend, **options)
        table = data.to_arrow()
        steady_s = time.perf_counter() - t0
        stats = data.metrics.device_stats.as_dict()
        check_device_use(stats, backend)
        check(data.metrics.backend == backend, "metrics name another "
              "backend than the one asked for")
        say(phase=name, backend=backend, device_kind=device["kind"],
            bytes=data.metrics.bytes_read, records=table.num_rows,
            warmup_bytes=warm.metrics.bytes_read,
            warmup_s=round(warm_s, 3), compile_s=warm_stats["compile_s"],
            compiles=warm_stats["compiles"], steady_s=round(steady_s, 3),
            compiles_in_steady=stats["compiles"],
            compile_s_in_steady=stats["compile_s"],
            h2d_bytes=stats["h2d_bytes"], d2h_bytes=stats["d2h_bytes"],
            launches=stats["launches"], devices=stats["devices"],
            has_kernel=stats["has_kernel"],
            interpreted=stats["interpreted"],
            device_groups=stats["device_groups"],
            # batches with segment row masks that launched by redefine,
            # those the plan's widths kept whole, the rows by set
            partitioned_batches=stats["partitioned_batches"],
            declined_batches=stats["declined_batches"],
            set_rows=stats["set_rows"])
        tables[backend] = table

    host = read_cobol(path, backend="numpy", **options).to_arrow()
    sample_path = path + ".sample"
    idx = sample(path, sample_path, seed)
    oracle = read_cobol(sample_path, backend="host", **options).to_arrow()
    for backend, table in tables.items():
        check(table.equals(host),
              f"{name}: backend {backend!r} differs from the host kernels")
        check(table.take(pa.array(idx)).equals(oracle),
              f"{name}: backend {backend!r} differs from the scalar "
              f"oracle on the sample")
    say(phase=name, parity="ok", compared=sorted(tables),
        table_equals_host_kernels=True, records=host.num_rows,
        oracle_sample_records=len(idx), oracle_sample_equal=True)
    return tables, host


def phase_read_exp3(device: dict, path: str, warm_path: str, seed: int):
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK

    return phase_read(
        "read_exp3", device, path, warm_path,
        dict(EXP3_OPTIONS, copybook_contents=EXP3_COPYBOOK), ["pallas"],
        sample_rdw_file, seed)


def phase_read_exp1(device: dict, path: str, warm_path: str, seed: int):
    from cobrix_tpu.testing.generators import (EXP1_COPYBOOK,
                                               EXP1_RECORD_SIZE)

    def sample(src, dst, s):
        return sample_fixed_file(src, dst, EXP1_RECORD_SIZE, s)

    return phase_read(
        "read_exp1", device, path, warm_path,
        {"copybook_contents": EXP1_COPYBOOK}, ["pallas", "jax"],
        sample, seed)


def phase_compile_cache(device: dict) -> None:
    """Compile the exp3 decode from two decoders that have compiled
    nothing: the second build is the first one's program exactly, so
    JAX's persistent cache, wherever it was placed, should hand the
    executable back and leave only trace and lowering to pay."""
    import jax

    from cobrix_tpu.reader.columnar import ColumnarDecoder

    seconds = []
    for _ in range(2):
        decoder = ColumnarDecoder(exp3_copybook(), backend="pallas")
        extent = decoder.plan.max_extent
        shape = (decoder._device_block(1 << 30, extent), extent)
        compiled, built = decoder.device_program().compiled_for(
            jax.ShapeDtypeStruct(shape, np.uint8))
        check(built, "a fresh decoder had a compiled program already")
        seconds.append(round(compiled.compile_s, 3))
    say(phase="compile_cache", device_kind=device["kind"],
        shape=f"{shape[0]}x{shape[1]}", first_s=seconds[0],
        again_s=seconds[1],
        compile_cache_dir=jax.config.jax_compilation_cache_dir)


# -------------------------------------------------------- aggregate phase

def exp3_wide_records(path: str):
    """(file image, payload offsets, lengths) of the exp3 'C' records."""
    from cobrix_tpu import native

    raw = np.memmap(path, dtype=np.uint8, mode="r")
    offsets, lengths = native.rdw_scan(raw, big_endian=False)
    wide = lengths >= 1000
    return raw, offsets[wide], lengths[wide]


def exp3_copybook():
    from cobrix_tpu import parse_copybook
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK

    return parse_copybook(EXP3_COPYBOOK,
                          segment_redefines=["STATIC_DETAILS", "CONTACTS"])


def stream_aggregate(agg, raw, offsets, lengths, block: int):
    """Pack `block` records at a time with the product's native pack, put
    them on the aggregator's mesh, and submit; fetch when all are in
    flight. Returns (merged aggregates, facts about the run)."""
    import jax

    from cobrix_tpu import native
    from cobrix_tpu.parallel import merge_aggregates

    program = agg.device_program()
    facts = {"h2d_bytes": 0, "blocks": 0, "compile_s": 0.0,
             "input_devices": set(), "output_devices": set()}
    pending = []
    t0 = time.perf_counter()
    for i in range(0, len(offsets), block):
        mat = native.pack_records(raw, offsets[i:i + block],
                                  lengths[i:i + block], agg.record_extent)
        x, n = agg.put(mat, block=block)
        x.block_until_ready()
        compiled, built = program.compiled_for(x, np.int32(n))
        if built:
            facts["compile_s"] += compiled.compile_s
        facts["has_kernel"] = compiled.has_kernel
        tree = agg.submit(x, n)
        facts["h2d_bytes"] += x.nbytes
        facts["blocks"] += 1
        facts["input_devices"].update(str(d) for d in x.devices())
        facts["output_devices"].update(
            str(d) for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices())
        pending.append(tree)
    parts = [agg.fetch(tree) for tree in pending]
    facts["wall_s"] = time.perf_counter() - t0
    facts["d2h_bytes"] = sum(
        leaf.nbytes for tree in pending
        for leaf in jax.tree_util.tree_leaves(tree))
    facts["interpreted"] = program.interpreted
    return merge_aggregates(parts), facts


def host_aggregates(table, fields) -> dict:
    """count/sum/min/max of the OCCURS fields from the host kernels'
    Arrow table."""
    import pyarrow.compute as pc

    detail = pc.list_flatten(pc.struct_field(
        table.column("COMPANY_DETAILS"),
        ["STATIC_DETAILS", "STRATEGY", "STRATEGY_DETAIL"]))
    out = {}
    for name in fields:
        values = pc.struct_field(detail, name)
        out[name] = {"count": pc.count(values).as_py(),
                     "sum": float(pc.sum(values).as_py()),
                     "min": float(pc.min(values).as_py()),
                     "max": float(pc.max(values).as_py())}
    return out


def check_aggregates(got: dict, want: dict, what: str) -> dict:
    """Counts, minima and maxima are equal; sums agree to SUM_RTOL.
    Returns the absolute sum differences, to be printed."""
    diffs = {}
    for name, w in want.items():
        g = got[name]
        for key in ("count", "min", "max"):
            check(g[key] == w[key],
                  f"{what}: {name}.{key} {g[key]} != {w[key]}")
        diffs[name] = abs(g["sum"] - w["sum"])
        check(diffs[name] <= SUM_RTOL * abs(w["sum"]),
              f"{what}: {name}.sum {g['sum']} != {w['sum']}")
    return diffs


def phase_device_aggregate(device: dict, path: str, host_table) -> None:
    import jax

    from cobrix_tpu.parallel import DeviceAggregator, data_mesh

    fields = ["NUM1", "NUM2"]
    # the first device alone: data_mesh() would take every device there is
    agg = DeviceAggregator(exp3_copybook(), columns=fields,
                           active_segment="STATIC_DETAILS",
                           mesh=data_mesh(1), backend="pallas")
    raw, offsets, lengths = exp3_wide_records(path)
    got, facts = stream_aggregate(agg, raw, offsets, lengths,
                                  AGGREGATE_BLOCK)
    first = jax.devices()[0]
    check(facts["input_devices"] == {str(first)}
          and facts["output_devices"] == {str(first)},
          f"device_aggregate ran on {facts['input_devices']} -> "
          f"{facts['output_devices']}, expected {first}")
    check_program("device_aggregate", facts["has_kernel"],
                  facts["interpreted"])
    diffs = check_aggregates(got, host_aggregates(host_table, fields),
                             "device_aggregate vs host decode")
    say(phase="device_aggregate", backend="pallas",
        device_kind=device["kind"], bytes=int(lengths.sum()),
        records=len(offsets), blocks=facts["blocks"],
        block_records=AGGREGATE_BLOCK,
        compile_s=round(facts["compile_s"], 3),
        steady_s=round(facts["wall_s"] - facts["compile_s"], 3),
        h2d_bytes=facts["h2d_bytes"], d2h_bytes=facts["d2h_bytes"],
        devices=sorted(facts["output_devices"]),
        has_kernel=facts["has_kernel"], interpreted=facts["interpreted"],
        aggregates=got, sum_abs_diff_vs_host=diffs, parity="ok")


# ------------------------------------------------------------ serve phase

def phase_serve(device: dict, path: str, reference) -> None:
    """A ScanServer inside this process (the chip belongs to one process)
    answers SERVE_REQUESTS fetches of the exp3 file on the pallas backend;
    each table equals the in-process one, schema metadata included."""
    from cobrix_tpu.serve import ScanServer, stream_scan
    from cobrix_tpu.testing.generators import EXP3_COPYBOOK

    server = ScanServer(enable_http=False).start()
    try:
        for request in range(SERVE_REQUESTS):
            t0 = time.perf_counter()
            with stream_scan(server.address, path, tenant="chip_smoke",
                             backend="pallas",
                             copybook_contents=EXP3_COPYBOOK,
                             **EXP3_OPTIONS) as stream:
                table = stream.table()
                summary = stream.summary
            wall_s = time.perf_counter() - t0
            check(table.equals(reference, check_metadata=True),
                  f"serve: request {request} differs from the "
                  f"in-process read")
            stats = summary["metrics"]["device"]
            check_device_use(stats, "pallas")
            say(phase="serve", request=request, backend="pallas",
                device_kind=device["kind"],
                bytes=summary["metrics"]["bytes_read"],
                records=table.num_rows, wall_s=round(wall_s, 3),
                compile_s=stats["compile_s"], compiles=stats["compiles"],
                h2d_bytes=stats["h2d_bytes"], d2h_bytes=stats["d2h_bytes"],
                launches=stats["launches"], devices=stats["devices"],
                has_kernel=stats["has_kernel"],
                interpreted=stats["interpreted"],
                table_equals_in_process=True)
    finally:
        server.stop()


# ------------------------------------------------------- four-chip phase

def phase_sharded(device: dict, path: str, n_devices: int) -> None:
    """The repo's multi-chip path — DeviceAggregator and
    ShardedColumnarDecoder over data_mesh(n_devices) — against the same
    over a one-device mesh: equal aggregates, equal decoded planes, and
    every device of the mesh holding its shard of the input."""
    from cobrix_tpu import native
    from cobrix_tpu.parallel import (DeviceAggregator,
                                     ShardedColumnarDecoder, data_mesh)

    copybook = exp3_copybook()
    fields = ["NUM1", "NUM2"]
    raw, offsets, lengths = exp3_wide_records(path)
    results = {}
    for nd in (n_devices, 1):
        mesh = data_mesh(nd)
        agg = DeviceAggregator(copybook, columns=fields,
                               active_segment="STATIC_DETAILS",
                               mesh=mesh, backend="pallas")
        got, facts = stream_aggregate(agg, raw, offsets, lengths,
                                      AGGREGATE_BLOCK)
        check(len(facts["input_devices"]) == nd,
              f"aggregate over {nd} device(s) put its input on "
              f"{facts['input_devices']}")
        check_program(f"aggregate over {nd} device(s)",
                      facts["has_kernel"], facts["interpreted"])

        decoder = ShardedColumnarDecoder(
            copybook, mesh=mesh, active_segment="STATIC_DETAILS",
            backend="pallas")
        n = min(SHARDED_BATCH, len(offsets))
        mat = native.pack_records(raw, offsets[:n], lengths[:n],
                                  decoder.plan.max_extent)
        x, _ = decoder.put(mat)
        x.block_until_ready()
        shard_bytes = {str(s.device): s.data.nbytes
                       for s in x.addressable_shards}
        check(len(shard_bytes) == nd and all(shard_bytes.values()),
              f"decode over {nd} device(s): input shards {shard_bytes}")
        program = decoder.device_program()
        compiled, _ = program.compiled_for(x)
        check_program(f"decode over {nd} device(s)", compiled.has_kernel,
                      program.interpreted)
        t0 = time.perf_counter()
        outs = compiled.executable(x)
        # the columns' arrays as the host reads them: the matrix of the
        # strings' code points crosses in another shape on one device
        # than over a mesh (columnar.POINTS_LANES)
        columns = decoder.collect_outputs(outs, n, points=program.points)
        planes = [arr for _, out in sorted(columns.items())
                  for _, arr in sorted(out.items())
                  if isinstance(arr, np.ndarray)]
        decode_s = time.perf_counter() - t0
        results[nd] = (got, planes)
        say(phase="sharded", mesh_devices=nd, backend="pallas",
            device_kind=device["kind"],
            aggregate_bytes=int(lengths.sum()),
            aggregate_records=len(offsets),
            aggregate_compile_s=round(facts["compile_s"], 3),
            aggregate_steady_s=round(
                facts["wall_s"] - facts["compile_s"], 3),
            aggregate_devices=sorted(facts["input_devices"]),
            aggregates=got, decode_records=n, decode_bytes=mat.nbytes,
            decode_compile_s=round(compiled.compile_s, 3),
            decode_s=round(decode_s, 3),
            decode_input_shard_bytes=shard_bytes,
            has_kernel=compiled.has_kernel,
            interpreted=program.interpreted)

    (many, many_planes), (one, one_planes) = results[n_devices], results[1]
    diffs = check_aggregates(many, one,
                             f"{n_devices}-device vs 1-device aggregate")
    check(len(many_planes) == len(one_planes)
          and all(np.array_equal(a, b)
                  for a, b in zip(many_planes, one_planes)),
          f"decode over {n_devices} devices differs from one device")
    say(phase="sharded", parity="ok", sum_abs_diff=diffs,
        decoded_planes_equal=len(one_planes))


# ------------------------------------------------------------------ runs

def run_one_chip(device: dict, workdir: str, exp3_bytes: int,
                 exp1_bytes: int, seed: int) -> None:
    paths = {name: os.path.join(workdir, name + ".dat")
             for name in ("exp3", "exp3_warm", "exp1", "exp1_warm")}
    t0 = time.perf_counter()
    sizes = {
        "exp3": write_exp3(paths["exp3"], exp3_bytes, seed),
        "exp3_warm": write_exp3(paths["exp3_warm"],
                                min(exp3_bytes, EXP3_WARM_MIB * MIB), seed),
        "exp1": write_exp1(paths["exp1"], exp1_bytes, seed + 1000),
        "exp1_warm": write_exp1(paths["exp1_warm"],
                                min(exp1_bytes, EXP1_WARM_MIB * MIB),
                                seed + 1000),
    }
    say(phase="setup", generated_bytes=sizes, seed=seed,
        generate_s=round(time.perf_counter() - t0, 1),
        cut="upstream exp3 and exp1 are 40 GB each; cut to these sizes "
            "for the run's 1200 s limit")

    tables, host = phase_read_exp3(device, paths["exp3"],
                                   paths["exp3_warm"], seed)
    phase_compile_cache(device)
    phase_device_aggregate(device, paths["exp3"], host)
    del host
    phase_serve(device, paths["exp3"], tables["pallas"])
    del tables
    phase_read_exp1(device, paths["exp1"], paths["exp1_warm"], seed)


def run_sharded(device: dict, workdir: str, exp3_bytes: int, seed: int,
                n_devices: int) -> None:
    path = os.path.join(workdir, "exp3.dat")
    size = write_exp3(path, exp3_bytes, seed)
    say(phase="setup", generated_bytes={"exp3": size}, seed=seed)
    phase_sharded(device, path, n_devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--exp3-mib", type=float, default=EXP3_MIB,
                    help="size of the generated exp3 file")
    ap.add_argument("--exp1-mib", type=float, default=EXP1_MIB,
                    help="size of the generated exp1 file")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded phase, on a four-chip host")
    args = ap.parse_args(argv)

    build_native()
    device = find_device("tpu", args.chips)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 1:
            run_one_chip(device, workdir, int(args.exp3_mib * MIB),
                         int(args.exp1_mib * MIB), args.seed)
        else:
            run_sharded(device, workdir, int(args.exp3_mib * MIB),
                        args.seed, args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
