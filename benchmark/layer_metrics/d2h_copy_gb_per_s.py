"""Layer device_link: the rate a fetching thread sees: bytes brought
back from the device over the fetching threads' own seconds inside
`d2h_wait.copy` (`DeviceStats.d2h_bytes / d2h_copy_thread_s`: seconds on
each thread's clock, summed over launches and threads, not split among
them), in 10^9 bytes a second, over the window's scans. The copies are
queued before the wait for the outputs, so the seconds are what remains
of them after the thread's wake-up: the rate is no less than the link
gave. None where no scan counts the seconds (a program from before them,
no device record) or they come to nothing."""
from ..harness import GB, completed


def read(record: dict):
    stats = [r["device"] for r in completed(record)
             if "d2h_copy_thread_s" in (r.get("device") or {})]
    seconds = sum(s["d2h_copy_thread_s"] for s in stats)
    if not seconds:
        return None
    return sum(s["d2h_bytes"] for s in stats) / seconds / GB
