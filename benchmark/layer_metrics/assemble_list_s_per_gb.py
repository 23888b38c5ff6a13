"""Layer arrow_assembly: seconds building OCCURS arrays (stage
`assemble.list`, the slots of its elements included: exp3's `OCCURS
2000`) per 10^9 input bytes, the median over the window's scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "assemble.list")
