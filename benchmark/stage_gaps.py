"""Where the chip waited, by the program's own stage.

    python3 -m benchmark.stage_gaps <trace.xplane.pb | planes.json>

`trace_reduce.reduce_trace` labels the traced window's idle gaps by the
benchmark's outermost span and by whether a thread was inside
`cobrix_decode`. This goes one level down: the idle seconds by the
INNERMOST stage of the program (a `cobrix.<stage>` TraceAnnotation of
`cobrix_tpu.profiling.Stage`) that a host thread was in at the time, which
is the stage whose own time the chip was waiting through. Where several
threads were inside stages at once (the shards of one read, the table
builds of one `.to_arrow()`, a pipeline's stage threads) each instant is
split evenly among them, as the program's own `stage_s` counters split
it; a thread inside `cobrix.pool_wait` only waits for those others and
takes no share. Where no thread was in any stage the time goes to
`outside_any_stage`. The rows add up to the window's idle seconds.

Nothing calls this file; PERF.md section 5 is filled from what it prints,
and a later `benchmark` issue may fold it into `trace_reduce` (PERF.md,
Open questions).
"""
import json
import sys

from . import trace_reduce as tr

STAGE_PREFIX = "cobrix."
POOL_WAIT = "cobrix.pool_wait"
OUTSIDE_STAGES = "outside_any_stage"


def _keep(name: str) -> bool:
    return (name.startswith((STAGE_PREFIX, tr.BENCH_PREFIX))
            or name in tr.PROGRAM_SPANS)


def load_xplane(path: str) -> list:
    """The planes of an .xplane.pb in `trace_reduce`'s plain-list form,
    host lines cut down to the benchmark's and the program's spans, the
    `cobrix.*` stages among them."""
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith(tr.DEVICE_PLANE_PREFIX)
        if not device and plane.name != tr.HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, e.start_ns, e.duration_ns]
                      for e in line.events if device or _keep(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def innermost(spans: list) -> list:
    """[(start, end, name)] disjoint and sorted: at each instant covered
    by the properly nested `spans` of one thread, the innermost one."""
    pieces = []
    stack = []          # open spans, outermost first: [name, end]
    at = None           # where the piece now being covered began

    def close_until(t):
        nonlocal at
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        if end <= start:
            continue
        close_until(start)
        if stack and start > at:
            pieces.append((at, start, stack[-1][0]))
        stack.append([name, end])
        at = start
    close_until(float("inf"))
    return pieces


def idle_intervals(planes: list):
    """(window lo, window hi, the disjoint intervals inside it in which no
    device ran an operation), or None where the trace holds no device
    plane or no traced window."""
    windows = [(start, start + duration)
               for plane in planes if plane["name"] == tr.HOST_PLANE
               for line in plane["lines"]
               for name, start, duration in line["events"]
               if name == tr.WINDOW_SPAN]
    devices = [p for p in planes
               if p["name"].startswith(tr.DEVICE_PLANE_PREFIX)]
    if not windows or not devices:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    busy = [(start, start + duration)
            for plane in devices for line in plane["lines"]
            if line["name"] in tr.OP_LINES
            for _, start, duration in line["events"]]
    return lo, hi, tr.complement(tr.clip(tr.union(busy), lo, hi), lo, hi)


def stage_gaps(planes: list) -> dict:
    """{"window_s", "idle_s", "threads": host threads that ran a stage,
    "by_stage": [[stage, idle seconds], ...] largest first,
    "under_a_stage": the share of the idle seconds with some thread
    inside a stage}, or None as `idle_intervals`."""
    found = idle_intervals(planes)
    if found is None:
        return None
    lo, hi, idle = found
    # one sweep over every edge: the idle gaps' and, per thread, those of
    # the pieces in which one stage is the innermost
    edges = []
    for start, end in idle:
        edges += [(start, 0, None), (end, 1, None)]
    threads = 0
    for plane in planes:
        if plane["name"] != tr.HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans = [(name, start, start + duration)
                     for name, start, duration in line["events"]
                     if name.startswith(STAGE_PREFIX)]
            threads += bool(spans)
            for start, end, name in innermost(spans):
                if name != POOL_WAIT:
                    edges += [(start, 2, name), (end, 3, name)]
    by_stage = {}
    working = {}            # stage -> threads whose innermost it is now
    is_idle = False
    at = lo
    for t, kind, name in sorted(edges, key=lambda e: e[0]):
        if is_idle and t > at:
            count = sum(working.values())
            for stage, n in working.items():
                by_stage[stage] = (by_stage.get(stage, 0.0)
                                   + (t - at) * n / count)
        at = t
        if kind < 2:
            is_idle = kind == 0
        else:
            working[name] = working.get(name, 0) + (1 if kind == 2 else -1)
            if not working[name]:
                del working[name]
    idle_ns = tr.total(idle)
    under = sum(by_stage.values())
    by_stage[OUTSIDE_STAGES] = idle_ns - under
    ranked = sorted(by_stage.items(), key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle_ns / 1e9,
            "threads": threads,
            "by_stage": [[name, ns / 1e9] for name, ns in ranked],
            "under_a_stage": under / idle_ns if idle_ns else 0.0}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    path = argv[0]
    if path.endswith(".json"):
        planes = tr.load_json(path)
        if isinstance(planes, dict):    # a recording with its note
            planes = planes["planes"]
    else:
        planes = load_xplane(path)
    gaps = stage_gaps(planes)
    if gaps is None:
        print("stage_gaps: no device plane or no traced window in " + path,
              file=sys.stderr)
        return 1
    print(json.dumps(gaps))
    print(f"window {gaps['window_s']:.3f} s, device idle "
          f"{gaps['idle_s']:.3f} s, {gaps['under_a_stage']:.1%} of it "
          f"under a {STAGE_PREFIX}* stage of {gaps['threads']} thread(s)")
    for name, seconds in gaps["by_stage"]:
        print(f"  {seconds:9.3f} s  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
