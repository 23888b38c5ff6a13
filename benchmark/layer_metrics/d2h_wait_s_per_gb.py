"""Layer device_link: seconds from the dispatch of a block's program
until its outputs are host memory (stages `launch` + `d2h_wait`), the
chip's own time included, per 10^9 input bytes, the median over the
window's scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "launch", "d2h_wait")
