"""Layer executor: seconds generating the Seg_Id columns per 10^9 input
bytes, the median over the window's scans: the levels and counters of
every record (stage `seg_id`: upstream's SegmentIdAccumulator over a
framed shard) and their strings in the Arrow table (stage
`assemble.seg_id`). None where the program counts no stage `seg_id`."""
from .stage_s import stage_s_per_gb
from ..harness import completed


def read(record: dict):
    if not any("seg_id" in ((r.get("device") or {}).get("stage_s") or {})
               for r in completed(record)):
        return None
    return stage_s_per_gb(record, "seg_id", "assemble.seg_id")
