"""Layer device_link: seconds copying what was fetched (stages `merge`,
the concatenation of the blocks' outputs, and `collect`, their cut to
column arrays) per 10^9 input bytes, the median over the window's
scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "merge", "collect")
