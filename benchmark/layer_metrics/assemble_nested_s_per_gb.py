"""Layer arrow_assembly: self seconds of the stage `assemble.list.nested`
per 10^9 input bytes, the median over the window's scans that ran it: the
list column of a variable array of variable arrays built from its element
rows (the element struct over every element, wrapped by the outer
counts' offsets; reader/arrow_out.nested_list). NOT in it: the stages
that fire beneath it under their own names (`assemble.list` for the
elements' own lists, `assemble.scalar`, `.string`, `.decimal` for their
leaves), which `assemble_list_s_per_gb` and `assemble_s_per_gb` hold.
None where no scan ran the stage."""
from ..harness import GB, completed, median


def read(record: dict):
    values = [r["device"]["stage_s"]["assemble.list.nested"] / r["bytes"]
              * GB for r in completed(record)
              if "assemble.list.nested" in ((r.get("device") or {})
                                            .get("stage_s") or {})]
    return median(values) if values else None
