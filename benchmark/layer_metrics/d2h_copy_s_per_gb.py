"""Layer device_link: self seconds of the stage `d2h_wait.copy`
(`jax.device_get` on outputs that exist on the device) per 10^9 input
bytes, the median over the window's scans. The copies home were queued
before the wait for the outputs and run while the thread wakes, so these
are the seconds that REMAIN of the copy over the link, and of whatever
the runtime does to hand back numpy arrays, once the thread is awake. A
share of the wall, split among the threads inside stages, that adds up
with the other stages to the scan: what the scan gets back at most if
the copy vanished. What one thread sat through is `d2h_copy_gb_per_s`'s
denominator; how long the link was in use, `d2h_link_busy_s_per_gb`.
None where the stage did not run (a program from before it, no launch)
or there is nothing to read."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "d2h_wait.copy") or None
