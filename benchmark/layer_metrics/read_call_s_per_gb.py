"""Layer executor: seconds inside `read_cobol()` per 10^9 input bytes,
the median over the window's scans (the benchmark's own span)."""
from ..harness import span_s_per_gb


def read(record: dict):
    return span_s_per_gb(record, "read_cobol_s")
