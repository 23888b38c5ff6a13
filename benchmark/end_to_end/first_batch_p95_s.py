"""95th percentile, over the window's requests, of request sent to the
first Arrow batch in the client's hands, on the client's clock."""
from ..harness import latency_p95


def read(record: dict):
    return latency_p95(record, "first", "first_batch_p95_s")
