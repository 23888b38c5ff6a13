"""Layer executor: self seconds of the stage `pack` (records packed or
zero-padded to the decode program's extent and block) per 10^9 input
bytes, the median over the window's scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "pack")
