"""Layer executor: self seconds of the stage `expand` (the host's share
of laying variable-size OCCURS records to the static layout: the rows'
expanded lengths from the counts the device read; on the host kernels
the expansion itself) per 10^9 input bytes, the median over the window's
scans that ran it. The device's share is inside the decode program
(scope `cobrix.expand`), and so inside `decode_roofline`. None where no
scan ran the stage."""
from ..harness import GB, completed, median


def read(record: dict):
    values = [r["device"]["stage_s"]["expand"] / r["bytes"] * GB
              for r in completed(record)
              if "expand" in ((r.get("device") or {}).get("stage_s") or {})]
    return median(values) if values else None
