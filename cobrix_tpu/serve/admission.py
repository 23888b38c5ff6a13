"""Admission control: per-tenant quotas + weighted fair-share queueing.

The workload-management shape of production query services (PAPERS.md:
"Amazon Redshift re-invented" WLM): a scan is either admitted
immediately (tenant below its concurrency quota AND the server below
its global cap), queued (bounded depth, bounded wait), or rejected with
a structured reason. When capacity frees, the next scan is picked by
weighted fair share — the waiting tenant with the smallest
served-work/weight virtual time goes first, so a tenant flooding the
queue cannot starve the others, and a tenant with weight 2 drains twice
as fast as one with weight 1.

The second quota dimension is bytes: `max_inflight_bytes` bounds how
much assembled-but-not-yet-written Arrow data one tenant's scans may
hold (the streaming reorder buffer + frames being written). Producers
BLOCK on the byte gate — backpressure, not rejection — and time out
into a scan error only after `byte_wait_timeout_s` of zero drain (a
stuck client must not pin server memory forever).

The third dimension is the process itself: when a memory budget is
configured (utils.pressure — the serve CLI's ``--memory-budget-mb``)
and RSS crosses the SHED watermark, admission stops absorbing work
instead of letting the OOM-killer end every tenant at once. New
requests are refused with a structured ``overloaded`` reason, and
queued waiters are shed lowest-weight-first (the fair-share weight is
also the keep-under-pressure priority) until the queue halves. Scans
already admitted keep running — shedding protects them.

Everything is condition-variable based and deadline-bounded: no wait in
this module is infinite.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Dict, Optional

from ..obs.metrics import serve_metrics


@dataclass(frozen=True)
class TenantQuota:
    """One tenant's admission limits."""

    # scans this tenant may run concurrently
    max_concurrent: int = 4
    # scans this tenant may hold waiting in the admission queue; the
    # (max_queued + 1)-th concurrent request is REJECTED, not queued
    max_queued: int = 16
    # fair-share weight (2.0 drains the queue twice as fast as 1.0)
    weight: float = 1.0
    # bytes of assembled Arrow data this tenant's scans may hold
    # in flight toward clients before producers block (0 = unbounded)
    max_inflight_bytes: int = 256 * 1024 * 1024
    # concurrent follow subscriptions (serve follow=true). Followers
    # are long-lived BY DESIGN — they hold a scan slot for hours — so
    # they get their own, tighter ceiling inside max_concurrent: a
    # tenant cannot park followers on every slot and starve its own
    # bounded scans
    max_followers: int = 2


class AdmissionRejected(Exception):
    """Structured admission refusal; `reason` is machine-readable."""

    def __init__(self, tenant: str, reason: str, detail: str):
        super().__init__(detail)
        self.tenant = tenant
        self.reason = reason


class _Waiter:
    __slots__ = ("tenant", "granted", "abandoned", "shed", "follower")

    def __init__(self, tenant: str, follower: bool = False):
        self.tenant = tenant
        self.granted = False
        self.abandoned = False
        self.shed = False  # evicted by overload shedding
        self.follower = follower  # long-lived follow subscription


class AdmissionController:
    """Admission decisions for one server process.

    `admit(tenant)` blocks (fairly, up to `queue_timeout_s`) until the
    scan may run and returns a ticket to pass to `release`; it raises
    AdmissionRejected when the tenant's queue is full or the wait times
    out. One controller serves every front-end (TCP, flight) of a
    ScanServer."""

    def __init__(self, default_quota: Optional[TenantQuota] = None,
                 quotas: Optional[Dict[str, TenantQuota]] = None,
                 max_concurrent_scans: int = 16,
                 queue_timeout_s: float = 30.0,
                 byte_wait_timeout_s: float = 60.0,
                 metrics: Optional[dict] = None,
                 pressure=None):
        self.default_quota = default_quota or TenantQuota()
        self.quotas = dict(quotas or {})
        self.max_concurrent_scans = max(1, int(max_concurrent_scans))
        self.queue_timeout_s = max(0.0, float(queue_timeout_s))
        self.byte_wait_timeout_s = max(0.0, float(byte_wait_timeout_s))
        # memory watermark source: an explicit utils.pressure
        # MemoryPressure, else the process-wide monitor (None installed
        # = never sheds)
        self._pressure = pressure
        self.scans_shed = 0
        self._m = metrics if metrics is not None else serve_metrics()
        self._cond = threading.Condition()
        self._active: Dict[str, int] = {}
        # per-tenant FIFO of waiters; OrderedDict keeps tenant order
        # deterministic when virtual times tie
        self._queues: "OrderedDict[str, deque]" = OrderedDict()
        # weighted fair share: work served per tenant / weight. New or
        # returning tenants start at the current floor so an idle spell
        # doesn't bank unbounded credit
        self._vtime: Dict[str, float] = {}
        self._inflight_bytes: Dict[str, int] = {}
        # long-lived follow subscriptions currently admitted, per
        # tenant (a subset of _active; bounded by quota.max_followers)
        self._followers: Dict[str, int] = {}

    def quota(self, tenant: str) -> TenantQuota:
        return self.quotas.get(tenant, self.default_quota)

    # -- overload shedding -----------------------------------------------

    def pressure_level(self) -> int:
        from ..utils.pressure import current_level

        if self._pressure is not None:
            return self._pressure.level()
        return current_level()

    def _shed_queued_locked(self) -> int:
        """Evict queued waiters lowest-weight-first until the queue is
        at most half its current depth (admitted scans are untouched —
        shedding exists to let them finish). Evicted waiters' admit()
        calls raise a structured ``overloaded`` rejection, newest
        request first within a tenant (the oldest waiter kept its place
        longest). Returns the count shed."""
        total = sum(len(q) for q in self._queues.values())
        if total == 0:
            return 0
        target = total // 2
        shed = 0
        tenants = sorted(self._queues,
                         key=lambda t: (self.quota(t).weight, t))
        for tenant in tenants:
            q = self._queues.get(tenant)
            while q and total - shed > target:
                waiter = q.pop()  # newest first
                waiter.shed = True
                shed += 1
            if q is not None and not q:
                self._queues.pop(tenant, None)
            if total - shed <= target:
                break
        if shed:
            self.scans_shed += shed
            self._cond.notify_all()
        return shed

    # -- scan admission --------------------------------------------------

    def admit(self, tenant: str, follower: bool = False) -> _Waiter:
        """Block until this scan may run; returns the ticket for
        `release`. Raises AdmissionRejected (queue_full / queue_timeout
        / follower_quota / overloaded) — never hangs past
        `queue_timeout_s`. `follower` marks a long-lived follow
        subscription: it holds an ordinary weighted scan slot, but is
        additionally bounded by the tenant's `max_followers` so parked
        subscriptions cannot starve the tenant's own bounded scans."""
        from ..utils.pressure import LEVEL_SHED

        quota = self.quota(tenant)
        t0 = time.monotonic()
        if follower:
            with self._cond:
                if self._followers.get(tenant, 0) >= quota.max_followers:
                    self._m["rejected"].labels(
                        tenant=tenant, reason="follower_quota").inc()
                    raise AdmissionRejected(
                        tenant, "follower_quota",
                        f"tenant '{tenant}' already holds "
                        f"{self._followers[tenant]} follow "
                        f"subscription(s) "
                        f"(max_followers={quota.max_followers}); close "
                        "one or raise the quota")
        if self.pressure_level() >= LEVEL_SHED:
            # over the memory shed watermark: refuse new work AND shed
            # queued waiters (lowest weight first) so admitted scans
            # keep their memory and finish — the alternative is the
            # OOM-killer ending every tenant at once
            with self._cond:
                shed = self._shed_queued_locked()
            self._m["rejected"].labels(
                tenant=tenant, reason="overloaded").inc()
            raise AdmissionRejected(
                tenant, "overloaded",
                f"server is over its memory budget (shedding load"
                f"{f', evicted {shed} queued scan(s)' if shed else ''});"
                " retry later or on another replica")
        with self._cond:
            if self._can_run_locked(tenant, quota, follower=follower) \
                    and not self._queues.get(tenant):
                self._grant_locked(tenant, follower=follower)
                self._observe_admit(tenant, t0)
                return _Waiter(tenant, follower=follower)
            q = self._queues.setdefault(tenant, deque())
            if len(q) >= quota.max_queued:
                self._m["rejected"].labels(
                    tenant=tenant, reason="queue_full").inc()
                raise AdmissionRejected(
                    tenant, "queue_full",
                    f"tenant '{tenant}' already has {quota.max_concurrent}"
                    f" active scan(s) and {len(q)} queued "
                    f"(max_queued={quota.max_queued}); retry later")
            waiter = _Waiter(tenant, follower=follower)
            q.append(waiter)
            self._m["queued"].inc()
            try:
                deadline = t0 + self.queue_timeout_s
                while not waiter.granted:
                    if waiter.shed:
                        self._prune_vtime_locked(tenant)
                        self._m["rejected"].labels(
                            tenant=tenant, reason="overloaded").inc()
                        raise AdmissionRejected(
                            tenant, "overloaded",
                            f"queued scan for tenant '{tenant}' shed "
                            "under memory pressure; retry later or on "
                            "another replica")
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        waiter.abandoned = True
                        self._remove_waiter_locked(tenant, waiter)
                        self._prune_vtime_locked(tenant)
                        self._m["rejected"].labels(
                            tenant=tenant, reason="queue_timeout").inc()
                        raise AdmissionRejected(
                            tenant, "queue_timeout",
                            f"scan for tenant '{tenant}' waited "
                            f"{self.queue_timeout_s:.1f}s in the "
                            "admission queue without a free slot")
                    self._cond.wait(remaining)
            finally:
                self._m["queued"].dec()
            self._observe_admit(tenant, t0)
            return waiter

    def release(self, ticket: _Waiter) -> None:
        with self._cond:
            tenant = ticket.tenant
            if ticket.follower:
                left = max(0, self._followers.get(tenant, 0) - 1)
                if left:
                    self._followers[tenant] = left
                else:
                    self._followers.pop(tenant, None)
            self._active[tenant] = max(0, self._active.get(tenant, 0) - 1)
            if not self._active[tenant]:
                self._active.pop(tenant)
            self._m["active"].dec()
            self._wake_next_locked()
            self._prune_vtime_locked(tenant)

    def _prune_vtime_locked(self, tenant: str) -> None:
        """Drop a fully-idle tenant's virtual time. Keeping it would (a)
        grow the dict one entry per tenant name ever seen and (b) make
        the stale entry the fair-share floor, handing the tenant banked
        credit when it returns — the opposite of the floor's intent. A
        returning tenant re-enters at the floor of the tenants actually
        competing."""
        if not self._active.get(tenant) and not self._queues.get(tenant):
            self._vtime.pop(tenant, None)

    def _observe_admit(self, tenant: str, t0: float) -> None:
        self._m["admitted"].labels(tenant=tenant).inc()
        self._m["queue_wait"].observe(time.monotonic() - t0)

    def _can_run_locked(self, tenant: str, quota: TenantQuota,
                        follower: bool = False) -> bool:
        total = sum(self._active.values())
        if follower and self._followers.get(tenant, 0) \
                >= quota.max_followers:
            return False
        return (total < self.max_concurrent_scans
                and self._active.get(tenant, 0) < quota.max_concurrent)

    def _grant_locked(self, tenant: str, follower: bool = False) -> None:
        self._active[tenant] = self._active.get(tenant, 0) + 1
        if follower:
            self._followers[tenant] = self._followers.get(tenant, 0) + 1
        self._m["active"].inc()
        # fair-share bookkeeping: one admitted scan = 1/weight of
        # virtual work, floored at the current minimum so returning
        # tenants don't replay banked idle time
        weight = max(1e-6, self.quota(tenant).weight)
        floor = min(self._vtime.values()) if self._vtime else 0.0
        self._vtime[tenant] = max(self._vtime.get(tenant, floor),
                                  floor) + 1.0 / weight

    def _remove_waiter_locked(self, tenant: str, waiter: _Waiter) -> None:
        q = self._queues.get(tenant)
        if q:
            try:
                q.remove(waiter)
            except ValueError:
                pass
            if not q:
                self._queues.pop(tenant, None)

    def _wake_next_locked(self) -> None:
        """Grant freed capacity to queued waiters, tenant-fairly: among
        tenants whose head-of-queue could run, pick the one with the
        lowest virtual time."""
        while True:
            best = None
            for tenant, q in self._queues.items():
                if not q:
                    continue
                if not self._can_run_locked(tenant, self.quota(tenant),
                                            follower=q[0].follower):
                    continue
                floor = min(self._vtime.values()) if self._vtime else 0.0
                vt = self._vtime.get(tenant, floor)
                if best is None or vt < best[1]:
                    best = (tenant, vt)
            if best is None:
                break
            tenant = best[0]
            waiter = self._queues[tenant].popleft()
            if not self._queues[tenant]:
                self._queues.pop(tenant, None)
            if waiter.abandoned:
                continue
            waiter.granted = True
            self._grant_locked(tenant, follower=waiter.follower)
        self._cond.notify_all()

    # -- the in-flight byte gate ----------------------------------------

    def acquire_bytes(self, tenant: str, n: int,
                      timeout_s: Optional[float] = None,
                      own_bytes: int = 0) -> None:
        """Block until `n` more in-flight bytes fit the tenant's budget
        (backpressure on the assembly stage). A single batch larger
        than the whole budget is admitted alone rather than deadlocking,
        and so is a batch whose caller already holds (`own_bytes`)
        everything the tenant has in flight: only that caller can drain
        those bytes, so making it wait for them is waiting for itself.
        Raises TimeoutError after `timeout_s` (default
        `byte_wait_timeout_s`) without drain — callers that can create
        drain themselves (OrderedBatchEmitter flushing past a
        newly-failed chunk) pass short slices and retry."""
        budget = self.quota(tenant).max_inflight_bytes
        if budget <= 0 or n <= 0:
            return
        wait_s = (self.byte_wait_timeout_s if timeout_s is None
                  else max(0.0, float(timeout_s)))
        deadline = time.monotonic() + wait_s
        last_held = None
        with self._cond:
            while True:
                held = self._inflight_bytes.get(tenant, 0)
                if held + n <= budget or held <= own_bytes:
                    self._inflight_bytes[tenant] = held + n
                    return
                if last_held is not None and held < last_held:
                    # the client IS draining, just slowly: observed
                    # progress re-arms the clock — the timeout fires
                    # only after byte_wait_timeout_s of ZERO drain, as
                    # documented
                    deadline = time.monotonic() + wait_s
                last_held = held
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"tenant '{tenant}' held {held} in-flight bytes "
                        f"against a {budget} byte budget for "
                        f"{wait_s:.0f}s without drain "
                        "(client too slow or gone)")
                self._cond.wait(min(remaining, 0.5))

    def inflight_bytes(self, tenant: str) -> int:
        """Current charged bytes — lets slice-waiting callers
        (OrderedBatchEmitter._acquire_gate) observe drain progress
        across their own short acquire attempts."""
        with self._cond:
            return self._inflight_bytes.get(tenant, 0)

    def release_bytes(self, tenant: str, n: int) -> None:
        if n <= 0:
            return
        with self._cond:
            held = self._inflight_bytes.get(tenant, 0)
            held = max(0, held - n)
            if held:
                self._inflight_bytes[tenant] = held
            else:
                self._inflight_bytes.pop(tenant, None)
            self._cond.notify_all()

    # -- introspection (healthz) ----------------------------------------

    def snapshot(self) -> dict:
        with self._cond:
            tenants = sorted(set(self._active) | set(self._queues)
                             | set(self._inflight_bytes))
            out = {
                "active_scans": sum(self._active.values()),
                "queued_scans": sum(len(q) for q in
                                    self._queues.values()),
                "max_concurrent_scans": self.max_concurrent_scans,
                "scans_shed": self.scans_shed,
                "tenants": {
                    t: {"active": self._active.get(t, 0),
                        "queued": len(self._queues.get(t, ())),
                        "followers": self._followers.get(t, 0),
                        "inflight_bytes":
                            self._inflight_bytes.get(t, 0)}
                    for t in tenants},
            }
        monitor = self._pressure
        if monitor is None:
            from ..utils.pressure import process_pressure

            monitor = process_pressure()
        if monitor is not None:
            out["pressure"] = monitor.snapshot()
        return out
