"""Layer executor: the share of an indexed scan's shards that got their
records' tables from the index pass instead of framing their own byte
range again (`DeviceStats.preframed_shards` over `preframed_shards` +
`self_framed_shards`), over the window's scans: 1.0 where the index pass
is the file's one framing (a dense RDW file), 0.0 where every shard
framed itself. None where the program counts neither (a read that cuts
no index shards, or a program from before the counters)."""
from ..harness import completed


def read(record: dict):
    stats = [r.get("device") or {} for r in completed(record)]
    preframed = sum(s.get("preframed_shards", 0) for s in stats)
    framed = sum(s.get("self_framed_shards", 0) for s in stats)
    if not preframed + framed:
        return None
    return preframed / (preframed + framed)
