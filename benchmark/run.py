#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one cell, one last line of JSON on standard output:
`correct`, `attempted`, `failed`, `metrics`, `device` and, in a traced
run, `breakdown`. With `--trace 0` the metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics. Lines of detail come
before it. Needs a TPU and as many chips as the cell asks for: without
them it exits non-zero and prints no result. `--rehearse` (never what the
driver calls) runs tiny sizes on whatever JAX finds, labelled as such.

This file names no cell, configuration, traffic mix or metric: each is a
file found by the name the manifest gives (README.md beside this file).
"""
import argparse
import json
import os
import shutil
import signal
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import checks, inputs, manifest  # noqa: E402
from benchmark.harness import (BenchFault, Run, Tracer, load_named, now,  # noqa: E402
                               process_age_s, say, sum_launches)

# a run exits within 360 s, its first in a checkout within 1200 s: past
# that something hangs, and a hung chip is worse than a failed run
HARD_LIMIT_S = 1150
# where the readers of each kind of metric live, one file per metric
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def build_native() -> None:
    """Build the program's native library if it is missing or older than
    its sources. Done before JAX is touched; refuses the numpy fallback,
    because framing and pack ahead of the chip are native in production."""
    from cobrix_tpu import native
    from cobrix_tpu.native import build

    if build.needs_build():
        ok, message = build.build()
        if not ok:
            raise BenchFault(message)
    if not native.available():
        raise BenchFault("the native library did not load")


def find_device(chips: int, rehearse: bool) -> dict:
    """Initialise JAX, once. A cell runs on a TPU with the chips it asks
    for; only a rehearsal takes what JAX finds."""
    import jax

    from cobrix_tpu.ops.device import ensure_compile_cache

    # every program of the cell goes to the persistent cache, the small
    # tail shapes too, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "first": str(devices[0]),
              "compile_cache_dir": ensure_compile_cache()}
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] != chips):
        raise BenchFault(f"the cell needs {chips} tpu chip(s), JAX found "
                         f"{device['count']} x {device['platform']}")
    return device


def compiled_in_window(run: Run) -> None:
    """A shape that compiles inside the window is a fault of the warm-up:
    no result."""
    window = run.record["window"]["requests"]
    compiles = sum((r.get("device") or {}).get("compiles", 0)
                   for r in window)
    if compiles:
        warm = sum_launches([r.get("device")
                             for r in run.record["warm"]["requests"]])
        new = sorted(set(sum_launches([r.get("device") for r in window]))
                     - set(warm))
        raise BenchFault(f"{compiles} compilation(s) inside the window; "
                         f"shapes the warm-up did not launch: {new}")


def reduce_trace(run: Run) -> None:
    """The traced part of the window, reduced; and the bytes scanned and
    needed inside it, each request weighted by its share of time there."""
    from benchmark import trace_reduce

    tracer = run.tracer
    path = tracer.trace_file()
    if path is None:
        return
    planes = trace_reduce.load_xplane(path)
    with open(os.path.join(run.out_dir, "trace_planes.json"), "w") as f:
        json.dump(planes, f)
    reduced = trace_reduce.reduce_trace(planes)
    if reduced is None:
        say(phase="trace", note="no device plane in the trace",
            planes=[p["name"] for p in planes])
        return
    scanned = needed = 0.0
    for r in run.record["window"]["requests"]:
        if not r["ok"]:
            continue
        inside = (min(r["done"], tracer.stopped)
                  - max(r["sent"], tracer.started))
        share = max(0.0, inside) / max(r["done"] - r["sent"], 1e-9)
        scanned += share * r["bytes"]
        needed += share * (r["bytes"] + r["table_nbytes"])
    reduced.update(scanned_bytes=scanned, needed_bytes=needed)
    run.record["trace"] = reduced
    say(phase="trace", **reduced)


def read_metrics(run: Run, declared: list, kind: str) -> dict:
    """Each declared metric through its own reader; one that finds nothing
    to read is left out."""
    metrics = {}
    for metric in declared:
        value = load_named(READERS[kind], metric["name"]).read(run.record)
        if value is not None:
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}
    return metrics


def device_line(run: Run) -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    line = {"platform": run.device["platform"], "kind": run.device["kind"],
            "count": run.device["count"], "memory_peak_bytes": peak}
    if run.rehearse:
        line["rehearsal"] = True
    trace = run.record.get("trace")
    if trace:
        line.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    say(phase="device", memory_stats=stats,
        peak_host_rss_mb=_peak_rss_mb())
    return line


def _peak_rss_mb() -> float:
    import resource

    own = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return round(own / 1024.0, 1)


def run_cell(run: Run, spec: dict) -> dict:
    cell = run.cell
    build_native()
    run.generator = load_named("generators", run.config["generator"])
    t0 = now()
    run.files = inputs.make(run.config, run.traffic, run.workdir, run.seed,
                            run.scale)
    say(phase="setup", generated_bytes=[f["bytes"] for f in run.files],
        generate_s=round(now() - t0, 3), seed=run.seed)

    run.device = run.record["device"] = find_device(cell["chips"],
                                                    run.rehearse)
    say(phase="setup", device=run.device)
    run.tracer = Tracer(run.trace, run.out_dir)
    driver = load_named("drivers", run.traffic["driver"]).Driver(run)
    try:
        driver.set_up()
        t0 = now()
        warm = driver.warm_up()
        failed_warm = [r["error"] for r in warm if not r["ok"]]
        if failed_warm:
            raise BenchFault(f"warm-up failed: {failed_warm[:3]}")
        run.record["warm"] = {"requests": warm, "seconds": now() - t0}
        say(phase="warm_up", seconds=round(now() - t0, 3),
            requests=len(warm),
            launches=sum_launches([r["device"] for r in warm]),
            compiles=sum(r["device"]["compiles"] for r in warm),
            compile_s=round(sum(r["device"]["compile_s"] for r in warm), 3))

        run.record["setup_s"] = process_age_s()
        run.record["window"] = driver.window(run.seconds)
        run.record["window"]["seconds"] = run.seconds
        run.tracer.stop()
        requests = run.record["window"]["requests"]
        say(phase="window", requests=len(requests),
            failed=[r["error"] for r in requests if not r["ok"]][:5],
            launches=sum_launches([r.get("device") for r in requests]),
            setup_s=round(run.record["setup_s"], 3))
        compiled_in_window(run)
        if run.trace:
            reduce_trace(run)
        failures = driver.check(checks.check_files)
    finally:
        driver.close()

    kind = "per_layer" if run.trace else "end_to_end"
    declared = manifest.metrics_of(cell["name"], spec, kind)
    result = {
        "correct": not failures,
        "attempted": len(requests),
        "failed": sum(1 for r in requests if not r["ok"]),
        "metrics": read_metrics(run, declared, kind),
        "device": device_line(run),
    }
    trace = run.record.get("trace")
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; not a result")
    ap.add_argument("--validate", action="store_true",
                    help="check BENCHMARK.json against the driver's rules")
    args = ap.parse_args(argv)

    spec = manifest.load()
    if args.validate:
        bad = manifest.problems(spec)
        print("\n".join(bad) or "BENCHMARK.json: no problem found")
        return 1 if bad else 0
    if not args.workload:
        ap.error("--workload is required")
    cell = manifest.find(spec["workloads"], args.workload, "workload")
    config_entry = manifest.find(spec["configs"], cell["config"], "config")
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    traffic = manifest.load_json("traffic", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds is not None \
        else float(spec["run_seconds"])

    def too_long(signum, frame):
        raise BenchFault(f"the run passed {HARD_LIMIT_S} s")

    signal.signal(signal.SIGALRM, too_long)
    signal.alarm(HARD_LIMIT_S)
    # traces and logs go where chiprun brings them back from, never into
    # what git would commit; inputs go under TMPDIR, outside the checkout
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark",
                           f"{cell['name']}.seed{args.seed}.trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="cobrix_benchmark_")
    try:
        run = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=seconds, trace=bool(args.trace),
                  rehearse=args.rehearse, workdir=workdir, out_dir=out_dir)
        result = run_cell(run, spec)
    finally:
        signal.alarm(0)
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFault as fault:
        print(f"benchmark: {fault}", file=sys.stderr)
        sys.exit(1)
