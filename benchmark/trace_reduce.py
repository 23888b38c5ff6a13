"""From a JAX profiler trace (.xplane.pb) to the benchmark's numbers.

What a TPU v5e trace of this program holds (looked at by hand, PR 23):

  plane "/device:TPU:0"   lines "XLA Modules" (one event per launch of a
                          compiled program, e.g. jit_decode_all(<hash>)),
                          "XLA Ops" (one event per HLO operation inside
                          it, named by its HLO text, "%fusion.14 = ..."),
                          "Async XLA Ops" (copy-start/copy-done pairs)
  plane "/host:CPU"       one line per host thread; TraceAnnotations of
                          the benchmark ("bench.*") and of the program
                          ("cobrix_decode") are events on them, beside the
                          runtime's own (compiler passes, transposes)

Event starts and durations are nanoseconds on one clock for all planes.

  busy      the union of the intervals of the device planes' operation
            events, clipped to the traced window, averaged over the device
            planes
  window    the span of the annotation WINDOW_SPAN, which harness.Tracer
            opens when it starts the profiler and closes before it stops
  idle gaps the window minus busy, cut at every boundary of a host span
            and labelled by what the host was doing: the benchmark's
            outermost span there and, beneath it, whether a thread was
            inside the program's decode ("cobrix_decode") or not
            ("host_outside_decode")

The reduction works on plain lists, `{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}`, so a recorded trace can be kept
small as JSON and the arithmetic tested without a profiler.
"""
import json

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench.trace_window"
PROGRAM_SPANS = ("cobrix_decode",)
BENCH_PREFIX = "bench."
OUTSIDE = "host_outside_decode"
NO_SPAN = "no_bench_span"
TOP = 10


def load_xplane(path: str) -> list:
    """The planes of an .xplane.pb as plain lists, host lines cut down to
    the spans the reduction reads."""
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, e.start_ns, e.duration_ns]
                      for e in line.events
                      if device or _is_span(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load_json(path: str) -> list:
    with open(path) as f:
        return json.load(f)


def _is_span(name: str) -> bool:
    return name.startswith(BENCH_PREFIX) or name in PROGRAM_SPANS


def union(intervals: list) -> list:
    """Sorted, disjoint [start, end) covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def complement(intervals: list, lo: float, hi: float) -> list:
    """[lo, hi) minus the disjoint sorted `intervals`."""
    gaps = []
    at = lo
    for s, e in intervals:
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    if hi > at:
        gaps.append([at, hi])
    return gaps


def short_op_name(name: str) -> str:
    """"%fusion.14 = u16[...] fusion(...)" -> "fusion.14": the operation's
    own name in the program, as the trace gives it."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def _host_spans(planes: list) -> list:
    spans = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, duration in line["events"]:
                if _is_span(name):
                    spans.append((name, start, start + duration))
    return spans


def _label(at: float, spans: list) -> str:
    """What the host was doing at the instant `at`."""
    inside = [(s, name) for name, s, e in spans
              if s <= at < e and name != WINDOW_SPAN]
    bench = sorted((s, n) for s, n in inside if n.startswith(BENCH_PREFIX))
    outer = bench[0][1] if bench else NO_SPAN
    for program_span in PROGRAM_SPANS:
        if any(n == program_span for _, n in inside):
            return f"{outer}/{program_span}"
    return f"{outer}/{OUTSIDE}"


def reduce_trace(planes: list) -> dict:
    """{"window_s", "busy_s", "devices", "device_ops", "idle_gaps",
    "launches"} of one trace, or None where it holds no device plane or
    no traced window."""
    spans = _host_spans(planes)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE_PREFIX)]
    if not windows or not devices:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)

    busy_ns = 0.0
    by_op = {}
    launches = {}
    all_busy = []
    for plane in devices:
        intervals = []
        for line in plane["lines"]:
            if line["name"] in OP_LINES:
                for name, start, duration in line["events"]:
                    intervals.append((start, start + duration))
                    if line["name"] == OP_LINES[0]:
                        op = short_op_name(name)
                        by_op[op] = by_op.get(op, 0.0) + duration
            elif line["name"] == MODULE_LINE:
                for name, start, duration in line["events"]:
                    if lo <= start < hi:
                        module = name.split("(", 1)[0]
                        launches[module] = launches.get(module, 0) + 1
        merged = clip(union(intervals), lo, hi)
        busy_ns += total(merged)
        all_busy.extend(merged)
    busy_ns /= len(devices)

    # idle: no device at all is running an operation
    gaps = complement(union(all_busy), lo, hi)
    edges = sorted({t for _, s, e in spans for t in (s, e) if lo < t < hi})
    idle = {}
    for s, e in gaps:
        cuts = [s] + [t for t in edges if s < t < e] + [e]
        for a, b in zip(cuts, cuts[1:]):
            label = _label((a + b) / 2.0, spans)
            idle[label] = idle.get(label, 0.0) + (b - a)

    def top(table: dict) -> list:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9] for name, ns in ranked]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "devices": len(devices), "device_ops": top(by_op),
            "idle_gaps": top(idle), "launches": launches}
