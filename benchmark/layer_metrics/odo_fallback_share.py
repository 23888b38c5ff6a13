"""Layer executor: the share of a scan's variable-size OCCURS records
that the row path walked one by one instead of the batched expansion
(`DeviceStats.odo_fallback_records` over `odo_records` +
`odo_fallback_records`), over the window's scans. None where the program
counts neither (a read without such records, or a program from before
the counters)."""
from ..harness import completed


def read(record: dict):
    stats = [r.get("device") or {} for r in completed(record)]
    walked = sum(s.get("odo_fallback_records", 0) for s in stats)
    batched = sum(s.get("odo_records", 0) for s in stats)
    if not walked + batched:
        return None
    return walked / (walked + batched)
