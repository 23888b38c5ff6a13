"""Layer executor: the share of a scan's records of a variable array of
variable arrays that the record walk decoded instead of element rows
(`DeviceStats.odo_nested_fallback_records` over `odo_nested_records` +
`odo_nested_fallback_records`), over the window's scans. None where the
program counts neither (a read without such an array, or a program from
before the counters)."""
from ..harness import completed


def read(record: dict):
    stats = [r.get("device") or {} for r in completed(record)]
    walked = sum(s.get("odo_nested_fallback_records", 0) for s in stats)
    framed = sum(s.get("odo_nested_records", 0) for s in stats)
    if not walked + framed:
        return None
    return walked / (walked + framed)
