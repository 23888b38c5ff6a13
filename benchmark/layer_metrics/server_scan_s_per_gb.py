"""Layer serve_session: the server's own seconds per request (trailer
`scan_s`, on the server's clock) per 10^9 request bytes, the median over
the window's requests."""
from ..harness import GB, completed, median


def read(record: dict):
    values = [r["trailer"]["scan_s"] / r["bytes"] * GB
              for r in completed(record)
              if (r.get("trailer") or {}).get("scan_s") is not None]
    return median(values) if values else None
