"""Layer executor: the share of a hierarchical scan's root rows that a
record walk assembled because the columnar assembly declined
(`DeviceStats.hier_row_path_roots` over `hier_roots` +
`hier_row_path_roots`), over the window's scans. None where the program
counts neither (no hierarchical read, or a program from before the
counters)."""
from ..harness import completed


def read(record: dict):
    stats = [r.get("device") or {} for r in completed(record)]
    walked = sum(s.get("hier_row_path_roots", 0) for s in stats)
    columnar = sum(s.get("hier_roots", 0) for s in stats)
    if not walked + columnar:
        return None
    return walked / (walked + columnar)
