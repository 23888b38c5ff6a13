"""Layer query: the share of a device aggregate's chunks that the host
answered because the device could not prove its partials exact
(`DeviceStats.query_fallback_chunks / query_chunks`), over the window's
rounds. None where the program counts no query chunks."""
from ..harness import completed


def read(record: dict):
    stats = [r["device"] for r in completed(record)
             if (r.get("device") or {}).get("query_chunks")]
    if not stats:
        return None
    return (sum(s["query_fallback_chunks"] for s in stats)
            / sum(s["query_chunks"] for s in stats))
