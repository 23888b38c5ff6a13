"""Layer arrow_assembly: seconds assembling a hierarchical read's nested
rows per 10^9 input bytes, the median over the window's scans: the self
times of the three stages `assemble.hier` (round `hierarchical_table`),
`assemble.hier.assign` (the nesting) and `assemble.hier.leaves` (the
structs, the `take`s and the numeric leaves built at positions). NOT in
it: the `assemble.string`, `.scalar` and `.decimal` stages that fire
nested inside `.leaves` where a leaf is built at full length; a stage's
seconds are self time, so those are `assemble_s_per_gb`'s (which sums
everything under `assemble`) and `assemble_string_s_per_gb`'s. None
where no scan ran the stage (no hierarchical read, or a program from
before the stage)."""
from ..harness import completed
from .stage_s import stage_s_per_gb


def read(record: dict):
    if not any("assemble.hier" in ((r.get("device") or {}).get("stage_s")
                                   or {}) for r in completed(record)):
        return None
    return stage_s_per_gb(record, "assemble.hier")
