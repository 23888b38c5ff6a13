"""Layer executor: seconds of the stage `plan_index` and those beneath
it (`plan_index.scan`: every RDW header of the file read once, on one
thread, before any shard starts; `plan_index.seg_ids`: the segment id of
every record, for the cut at roots; the stage's own: split arithmetic,
the index store, the generic generator) per 10^9 input bytes, the median
over the window's scans. 0.0 where a read plans no index; None where
there is nothing to read."""
from .stage_s import stage_s_per_gb


def read(record: dict):
    return stage_s_per_gb(record, "plan_index")
