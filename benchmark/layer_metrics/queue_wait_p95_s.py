"""Layer serve_session: 95th percentile of the seconds a request waited
for admission (trailer `queue_wait_s`, on the server's clock)."""
from ..harness import completed, percentile


def read(record: dict):
    values = [r["trailer"]["queue_wait_s"] for r in completed(record)
              if (r.get("trailer") or {}).get("queue_wait_s") is not None]
    return percentile(values, 95.0) if values else None
