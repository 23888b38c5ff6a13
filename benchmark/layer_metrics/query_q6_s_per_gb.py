"""Layer query: seconds of the driver's span `bench.query.q6` (one
`dataset(...).aggregate()` of the round's query `q6` over the whole
file) per 10^9 file bytes, the median over the window's rounds."""
from ..harness import GB, completed, median


def read(record: dict):
    values = [r["query_s"]["q6"] / r["file_bytes"] * GB
              for r in completed(record) if "q6" in r.get("query_s", {})]
    return median(values) if values else None
