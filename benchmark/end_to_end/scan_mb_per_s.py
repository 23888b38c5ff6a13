"""Input file bytes (10^6 B) turned into complete Arrow tables per wall
second: the bytes of every scan or request that completed, over the time
from the window's start to the last completion."""
from ..harness import MB, completed, say


def read(record: dict):
    done = completed(record)
    if not done:
        return None
    elapsed = max(r["done"] for r in done) - record["window"]["start"]
    total = sum(r["bytes"] for r in done)
    say(metric="scan_mb_per_s", completed=len(done), bytes=total,
        elapsed_s=round(elapsed, 4))
    return total / MB / elapsed
