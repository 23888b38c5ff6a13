"""Layer arrow_assembly: self seconds of every `assemble*` stage (the
pipelined engine's `assemble` and `assemble.list`, `.scalar`, `.decimal`,
`.string`, `.table`) per 10^9 input bytes, the median over the window's
scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "assemble")
