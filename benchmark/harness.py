"""What every driver, metric reader and check of the benchmark shares:
the run's state, the clock, the profiler switch, the proof that a scan
used the device, and the arithmetic of rates and percentiles.

Nothing here names a cell, a configuration, a traffic mix or a metric:
those are files found by the names BENCHMARK.json gives (README.md).
"""
import glob
import importlib
import json
import os
import time
from dataclasses import dataclass, field

from .trace_reduce import WINDOW_SPAN

GB = 1e9
MB = 1e6


class BenchFault(RuntimeError):
    """The run cannot give a result: no chip, a shape compiled inside the
    window, a driver that broke. The process exits non-zero, no last line."""


def say(**fields) -> None:
    """One JSON line of detail on standard output, before the last line."""
    print(json.dumps(fields, default=str), flush=True)


def now() -> float:
    """Seconds on CLOCK_MONOTONIC, which the processes of one machine
    share: the clients of a served cell stamp their requests with it."""
    return time.monotonic()


IMPORTED_AT = time.monotonic()


def process_age_s() -> float:
    """Seconds since this process was started, interpreter start-up and
    imports included, from /proc; where /proc does not give a start time
    that fits (a sandboxed kernel), since this module was imported."""
    since_import = time.monotonic() - IMPORTED_AT
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return since_import
    return age if 0.0 <= age - since_import < 5.0 else since_import


def load_named(kind: str, name: str):
    """The module benchmark/<kind>/<name>.py."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks, as numpy's default does."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    pos = (len(ordered) - 1) * q / 100.0
    low = int(pos)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (pos - low)


def median(values: list) -> float:
    return percentile(values, 50.0)


@dataclass
class Run:
    """One run of one cell: what was asked, what was made for it, and the
    record the metric readers read."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    workdir: str
    out_dir: str
    files: list = field(default_factory=list)   # {"path", "bytes", "facts"}
    device: dict = field(default_factory=dict)
    generator: object = None
    tracer: object = None
    # filled as the run goes; README.md lists the keys a reader may use
    record: dict = field(default_factory=dict)

    @property
    def scale(self) -> str:
        """The key of a configuration's or a traffic mix's sizes."""
        return "rehearse" if self.rehearse else "full"

    def reader_options(self) -> dict:
        return dict(self.config["reader_options"],
                    copybook_contents=self.generator.COPYBOOK)

    def reference_options(self, which: str) -> dict:
        options = dict(self.reader_options())
        options.update(self.config["reference_options"][which])
        return options


class Tracer:
    """The JAX profiler, switched by the driver round the part of the
    window that is traced. With `--trace 0` every call is a no-op. Spans
    are jax.profiler.TraceAnnotations: on the profiler's own clock, beside
    the device's operations, and next to free when no trace is on."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.dir = os.path.join(out_dir, "trace")
        self.started = None
        self.stopped = None
        self._window = None

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.enabled or self.started is not None:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        # the Python tracer logs every call: a trace of gigabytes and a
        # host several times slower. Host TraceMe spans stay on.
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = now()
        self._window = self.span(WINDOW_SPAN)
        self._window.__enter__()

    def stop(self) -> None:
        if self.started is None or self.stopped is not None:
            return
        import jax

        self._window.__exit__(None, None, None)
        self.stopped = now()
        jax.profiler.stop_trace()

    def trace_file(self):
        if self.stopped is None:
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def device_proof(stats, platform: str, first_device: str) -> str:
    """'' if `stats` (a read's device record, as_dict form) shows that the
    scan ran on the device as the platform promises, else what is wrong.
    On a TPU the compiled program holds the Mosaic kernel and nothing was
    interpreted; on the CPU rehearsal it is the other way round."""
    if not stats or not stats.get("launches"):
        return "no device launch"
    if stats["devices"] != [first_device]:
        return f"outputs lived on {stats['devices']}, not [{first_device}]"
    on_tpu = platform == "tpu"
    if stats["has_kernel"] is not on_tpu or stats["interpreted"] is on_tpu:
        return (f"has_kernel={stats['has_kernel']} "
                f"interpreted={stats['interpreted']} on {platform}")
    return ""


def sum_launches(records: list) -> dict:
    """Launches by padded shape over several device records."""
    total = {}
    for stats in records:
        for shape, n in (stats or {}).get("launches", {}).items():
            total[shape] = total.get(shape, 0) + n
    return total


def completed(record: dict) -> list:
    """The window's requests that completed and held."""
    return [r for r in record["window"]["requests"] if r["ok"]]


def latencies(record: dict, until: str) -> list:
    """Seconds from request sent to `until` ("first" or "done") over the
    window's requests; one that failed or was refused counts as the
    slowest of them all."""
    requests = record["window"]["requests"]
    held = [r[until] - r["sent"] for r in requests if r["ok"]]
    if not held:
        return []
    slowest = max(max(held), max(r["done"] - r["sent"] for r in requests))
    return held + [slowest] * (len(requests) - len(held))


def latency_p95(record: dict, until: str, metric: str):
    """The 95th percentile of `latencies`, its sample count and median said
    on an earlier line; None where no request held."""
    values = latencies(record, until)
    if not values:
        return None
    say(metric=metric, samples=len(values), median_s=round(median(values), 4),
        max_s=round(max(values), 4))
    return percentile(values, 95.0)


def span_s_per_gb(record: dict, key: str):
    """Median over the window's scans of the seconds under `key` per 10^9
    input bytes; None where no scan carries it."""
    values = [r[key] / r["bytes"] * GB for r in completed(record) if key in r]
    return median(values) if values else None


def link_bytes_per_input_byte(record: dict, key: str):
    """The program's DeviceStats count `key` over the window's scans, per
    input file byte; None where no scan has a device record."""
    done = [r for r in completed(record) if r.get("device")]
    if not done:
        return None
    return sum(r["device"][key] for r in done) / sum(r["bytes"] for r in done)
