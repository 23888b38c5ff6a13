"""Layer query: self seconds of the aggregate's own host stages per 10^9
bytes scanned, the median over the window's rounds: `query.bind` (specs,
filter and keys bound to the plan, the program built or found),
`query.merge` (the chunks' partials merged, the result shaped) and
`query.fallback` (a chunk answered by the host). None where the program
counts no such stage."""
from .stage_s import stage_s_per_gb
from ..harness import completed


def read(record: dict):
    if not any(name.startswith("query.")
               for r in completed(record)
               for name in ((r.get("device") or {}).get("stage_s") or {})):
        return None
    return stage_s_per_gb(record, "query")
