"""Layer device_link: seconds inside `jax.device_put` (stage `h2d`) per
10^9 input bytes, the median over the window's scans."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "h2d")
