"""Layer executor: self seconds of the stage `frame.elements` (the walk
of a variable array of variable arrays' counts, one step an outer slot
over a shard's records, and the elements' offsets and lengths it gives;
reader/element_rows.py) per 10^9 input bytes, the median over the
window's scans that ran it. None where no scan ran the stage (a read
without such an array, or a program from before the stage)."""
from ..harness import completed
from .stage_s import stage_s_per_gb


def read(record: dict):
    if not any("frame.elements" in ((r.get("device") or {}).get("stage_s")
                                    or {}) for r in completed(record)):
        return None
    return stage_s_per_gb(record, "frame.elements")
