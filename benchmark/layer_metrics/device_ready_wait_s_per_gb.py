"""Layer device_link: self seconds of the stage `d2h_wait.ready`
(`jax.block_until_ready` and nothing else: a fetching thread waits until
its launch's outputs exist on the device: the rest of the H2D copy, the
program's run, the thread's wake-up; the copies home are queued before
it, in `d2h_wait`'s own time, and start when the outputs exist) per 10^9
input bytes, the median over the window's scans. With
`d2h_copy_s_per_gb`, `d2h_wait`'s own time and the stage `launch` it is
what `d2h_wait_s_per_gb` reads as one number. None where the stage did
not run (a program from before it, no launch) or there is nothing to
read."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "d2h_wait.ready") or None
