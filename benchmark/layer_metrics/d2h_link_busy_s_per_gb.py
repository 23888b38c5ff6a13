"""Layer device_link: the link's own busy time: wall seconds in which at
least one thread of the read was inside `d2h_wait.copy`
(`DeviceStats.d2h_copy_busy_s`) per 10^9 input bytes, the median over
the window's scans. Whole wall seconds, where `d2h_copy_s_per_gb` is a
share split with the read's other threads: the two are equal on one
fetching thread (`exp1_read`) and up to twice apart on six; against the
scan's own seconds this one says what overlapping fetches has left to
give, and `d2h_copy_thread_s` over it is how many threads fetch at once.
Like those seconds, what remains of the copies after the thread's
wake-up. None where no scan counts it (a program from before the
counter, or no device record)."""
from ..harness import GB, completed, median


def read(record: dict):
    values = [r["device"]["d2h_copy_busy_s"] / r["bytes"] * GB
              for r in completed(record)
              if "d2h_copy_busy_s" in (r.get("device") or {})]
    return median(values) if values else None
