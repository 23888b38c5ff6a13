"""Layer executor: self seconds of the stages `read` (the file's bytes
into memory) and `frame` (the RDW scan, or the fixed-length record
matrix) per 10^9 input bytes, the median over the window's scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "read", "frame")
