"""Layer arrow_assembly: seconds turning the fetched code points of
string fields into Arrow string arrays (stage `assemble.string`) per
10^9 input bytes, the median over the window's scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "assemble.string")
