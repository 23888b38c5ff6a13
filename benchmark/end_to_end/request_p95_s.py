"""95th percentile, over the window's requests, of request sent to last
batch received and the stream closed, on the client's clock."""
from ..harness import latency_p95


def read(record: dict):
    return latency_p95(record, "done", "request_p95_s")
