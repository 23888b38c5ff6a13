"""Shared by the per-layer metrics that read the program's stage counters
(`DeviceStats.stage_s`: self seconds by stage, in a request's `device`
record). Not a metric itself.

A stage's seconds are self time on the thread that ran it, and an instant
in which several threads of the read were inside stages is split evenly
among them: the stages of a read add up to at most the wall of
`read_cobol()` plus `.to_arrow()`, of a served request to its scan's.
"""
from ..harness import GB, completed, median


def per_scan(record: dict, seconds_of) -> list:
    """`seconds_of(request, stage_s)` per 10^9 input bytes for each of the
    window's completed scans whose program counted stages; [] where none
    did (no device record, or a program from before the counters)."""
    values = []
    for r in completed(record):
        stage_s = (r.get("device") or {}).get("stage_s")
        if stage_s is not None:
            values.append(float(seconds_of(r, stage_s)) / r["bytes"] * GB)
    return values


def stage_s_per_gb(record: dict, *stages: str):
    """Median over the window's scans of the seconds in `stages`, each
    with the stages named beneath it (`assemble` takes `assemble.list`),
    per 10^9 input bytes. 0.0 where none of them ran; None where there
    is nothing to read."""
    def seconds(_, stage_s):
        return sum(s for name, s in stage_s.items()
                   if any(name == want or name.startswith(want + ".")
                          for want in stages))

    values = per_scan(record, seconds)
    return median(values) if values else None
