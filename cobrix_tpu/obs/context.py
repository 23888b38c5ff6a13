"""Per-read observability context with explicit thread propagation.

One `ObsContext` bundles everything a read's execution threads need to
report into — the tracer (None when tracing is off), the metrics
registry's standard metric set, the progress tracker, and the per-read
compile-cache counter scope. `read_cobol` creates it and activates it on
the calling thread; the pipeline executor re-activates the SAME context
on every stage thread it spawns, and the var-len shard pool wraps its
scan closure — so attribution crosses thread pools deliberately instead
of leaking through process-globals (the plan_cache cross-read
contamination this replaces). Fork workers build their own context
(hosts.py) and ship spans home over the result pipes.

`current()` is a single thread-local read; every hot-path call site
gates on it being None, so the tracing-off cost is one attribute lookup.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

_tls = threading.local()


class ObsContext:
    """The read's observability bundle (any member may be None)."""

    __slots__ = ("tracer", "metrics", "progress", "cache_scope",
                 "io_stats", "field_costs", "pass_counts", "device_stats")

    def __init__(self, tracer=None, metrics: Optional[dict] = None,
                 progress=None, cache_scope=None, io_stats=None,
                 field_costs=None, pass_counts=None, device_stats=None):
        self.tracer = tracer
        self.metrics = metrics      # obs.metrics.scan_metrics() dict
        self.progress = progress    # obs.progress.ProgressTracker
        self.cache_scope = cache_scope  # plan.cache.CacheStatsScope
        self.io_stats = io_stats    # io.stats.IoStats (remote IO planes)
        # obs.fieldcost.FieldCostAccumulator — per-field/kernel-group
        # cost attribution; None = attribution off (the zero-cost
        # default: every timer site gates on this being None)
        self.field_costs = field_costs
        # profiling.PassCounters — fused-native-pass engagement counts
        # for the read (lands in ReadMetrics.as_dict()["native_passes"])
        self.pass_counts = pass_counts
        # profiling.DeviceStats — launches/bytes/compiles of the device
        # decode plane (lands in ReadMetrics.as_dict()["device"])
        self.device_stats = device_stats


def current() -> Optional[ObsContext]:
    return getattr(_tls, "ctx", None)


def count_pass(name: str, n: int = 1) -> None:
    """Record `n` engagements of a fused native pass against the active
    read's PassCounters; no-op outside a read (or when the read carries
    no metrics). Post-read assembly sites must NOT use this — the
    context is gone by then; they increment through the PassCounters
    reference their DecodedBatch captured at decode time."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None and ctx.pass_counts is not None:
        ctx.pass_counts.incr(name, n)


@contextlib.contextmanager
def activate(ctx: Optional[ObsContext]):
    """Install `ctx` as the thread's observability context (and its
    cache scope as the thread's cache-counter sink). Pass None for a
    no-op — call sites never need their own guard."""
    if ctx is None:
        yield
        return
    from ..plan.cache import activate_scope, deactivate_scope

    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    prev_scope = (activate_scope(ctx.cache_scope)
                  if ctx.cache_scope is not None else None)
    try:
        yield
    finally:
        _tls.ctx = prev
        if ctx.cache_scope is not None:
            deactivate_scope(prev_scope)
