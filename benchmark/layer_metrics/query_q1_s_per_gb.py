"""Layer query: seconds of the driver's span `bench.query.q1` (one
`dataset(...).aggregate()` of the round's query `q1` over the whole
file) per 10^9 file bytes, the median over the window's rounds."""
from ..harness import GB, completed, median


def read(record: dict):
    values = [r["query_s"]["q1"] / r["file_bytes"] * GB
              for r in completed(record) if "q1" in r.get("query_s", {})]
    return median(values) if values else None
