"""Layer arrow_assembly: self seconds of the stage `assemble.list.slots`
(an OCCURS built one array a slot and interleaved back into record
order: the route a list of structs of numerics and strings must not
take) per 10^9 input bytes, the median over the window's scans. 0.0
where no scan took the route; None where there is nothing to read."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "assemble.list.slots")
