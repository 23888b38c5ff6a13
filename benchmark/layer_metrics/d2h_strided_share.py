"""Layer device_link: the share of the bytes brought back from the
device that did not arrive C-contiguous (`DeviceStats.d2h_strided_bytes
/ d2h_bytes`: a result with a narrow minor dimension comes home
rows-minor), which the host has still to transpose in `merge`, `collect`
or an `assemble.*` stage, over the window's scans. None where no scan
counts them (a program from before the counter, no device record) or
nothing was fetched."""
from ..harness import completed


def read(record: dict):
    stats = [r["device"] for r in completed(record)
             if "d2h_strided_bytes" in (r.get("device") or {})]
    fetched = sum(s["d2h_bytes"] for s in stats)
    if not fetched:
        return None
    return sum(s["d2h_strided_bytes"] for s in stats) / fetched
