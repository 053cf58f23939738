"""StreamScheduler — request orchestration (paper Alg 1).

Receives requests, consults FlowGuard for placement, enqueues to the selected
stream pair's prefill queue, and tracks lifecycle transitions.  Health
tracking lives here too: dead/drained workers are excluded from routing and
their queued (not-yet-prefilled) requests are re-routed — the fault-tolerance
behaviour exercised by tests/test_fault_tolerance.py.

SLO control plane (``slo_routing=True``):

* **Routing** — submit() hands the router the request plus a per-worker
  queue-delay estimate (cost-model ticks of queued prefill work), so
  FlowGuard's TTFT-slack term can steer deadline-carrying requests away from
  backed-up queues.
* **EDF ordering** — prefill queues drain earliest-deadline-first (deadline =
  arrival + slo_ttft; best-effort requests sort last, FIFO among themselves)
  instead of strictly FIFO.
* **Admission guard** — a request whose TTFT slack is already negative when a
  prefill slot opens (its deadline has passed before service could start) is
  shed: serving it could only miss, while delaying feasible work behind it.
  Shed requests finish FAILED with ``error="slo_infeasible"`` and a
  ``slo_infeasible`` RequestRecord.
"""
from __future__ import annotations

import math
from collections import deque
from time import perf_counter
from typing import Callable, Deque, Dict, List, Optional, Protocol, Tuple

from jax.profiler import TraceAnnotation

from repro.core.flowguard import FlowGuard
from repro.core.metrics import PerformanceMonitor, RequestRecord
from repro.obs.spans import request_phases
from repro.obs.trace import (
    EV_EDF_POP,
    EV_ENQUEUE,
    EV_FAIL,
    EV_METRICS_STALE,
    EV_ROUTE,
    EV_SHED,
    EV_SUBMIT,
    SPAN_SUBMIT,
    NullRecorder,
)
from repro.serving.request import Request, RequestState


class Router(Protocol):
    def select(self, metrics, now, healthy=None, request=None,
               queue_delays=None, prefix_scores=None) -> Tuple[int, Dict[int, float]]: ...


def edf_deadline(req: Request) -> float:
    """EDF key: absolute TTFT deadline; best-effort requests sort last.

    Shared with the engine's chunked-prefill preemption: a partially
    prefilled request is parked when a queued arrival carries an earlier
    deadline, so both sides must rank by the same key.
    """
    if req.slo_ttft is None:
        return math.inf
    # tick-0 arrivals are real measurements: guard with `is not None`,
    # never truthiness (flowlint FL604)
    arrival = req.arrival_time if req.arrival_time is not None else 0.0
    return arrival + req.slo_ttft


class StreamScheduler:
    def __init__(
        self,
        n_pairs: int,
        router: Optional[Router] = None,
        monitor: Optional[PerformanceMonitor] = None,
        *,
        slo_routing: bool = False,
        delay_estimator: Optional[Callable[[Request], float]] = None,
        trace=None,
    ):
        self.n_pairs = n_pairs
        self.router: Router = router or FlowGuard()
        self.monitor = monitor or PerformanceMonitor(n_pairs)
        self.trace = trace if trace is not None else NullRecorder()
        self.prefill_queues: Dict[int, Deque[Request]] = {i: deque() for i in range(n_pairs)}
        self.healthy: Dict[int, bool] = {i: True for i in range(n_pairs)}
        self.routing_log: List[Tuple[str, int]] = []
        self.slo_routing = slo_routing
        self.delay_estimator = delay_estimator
        self.shed: List[Request] = []
        # chunked-prefill hooks (wired by the engine): requests parked in a
        # pair's chunk rows have left the prefill queue but still occupy the
        # prefill lane for ceil(remaining / chunk) ticks — routing signals
        # that ignored them would see a saturated lane as idle
        self.inflight_depth: Optional[Callable[[int], int]] = None
        self.inflight_delay: Optional[Callable[[int], float]] = None
        # paged-KV hook (wired by the engine): probes a pair's radix index for
        # a resident prefix and prices the hit as a saved-prefill fraction
        self.prefix_probe: Optional[Callable[[int, Request], float]] = None
        # routers predating the SLO plumbing (custom plugins) keep working:
        # only pass the extra kwargs to routers that declare them
        self._router_slo_aware = self._accepts_slo_kwargs(self.router)
        self._router_prefix_aware = self._accepts_prefix_kwarg(self.router)

    @staticmethod
    def _accepts_slo_kwargs(router: Router) -> bool:
        import inspect

        try:
            sig = inspect.signature(router.select)
        except (TypeError, ValueError):
            return False
        params = sig.parameters.values()
        if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
            return True
        names = {p.name for p in params}
        return {"request", "queue_delays"} <= names

    @staticmethod
    def _accepts_prefix_kwarg(router: Router) -> bool:
        import inspect

        try:
            sig = inspect.signature(router.select)
        except (TypeError, ValueError):
            return False
        params = sig.parameters.values()
        if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
            return True
        return "prefix_scores" in {p.name for p in params}

    # ---------------------------------------------------------------- routing
    def queue_delay(self, worker_id: int) -> float:
        """Estimated ticks of prefill service ahead of a new arrival: queued
        requests plus the in-flight chunked-prefill backlog (parked partials
        still owed lane turns)."""
        if self.delay_estimator is None:
            delay = float(len(self.prefill_queues[worker_id]))
        else:
            delay = sum(self.delay_estimator(r) for r in self.prefill_queues[worker_id])
        if self.inflight_delay is not None:
            delay += self.inflight_delay(worker_id)
        return delay

    def submit(self, req: Request, now: float) -> int:
        with TraceAnnotation(SPAN_SUBMIT):
            return self._submit(req, now)

    def _submit(self, req: Request, now: float) -> int:
        tr = self.trace
        # first submission only: a resubmitted request keeps its stamp
        if req.w_submit is None:
            req.w_submit = perf_counter()
        if tr.enabled:
            tr.emit(now, -1, EV_SUBMIT, req.request_id,
                    (req.prompt_len, req.slo_ttft, req.slo_tpot))
        healthy = [i for i, ok in self.healthy.items() if ok]
        # FlowGuard reads queue depth live (Alg 2: fresh values) — but a
        # derived refresh must NOT touch the staleness timestamp: a worker
        # that stopped reporting (crashed mid-collection, drained) would
        # otherwise score as fresh forever and keep attracting traffic
        for i in healthy:
            if tr.enabled and self.monitor.workers[i].is_stale(now):
                tr.emit(now, i, EV_METRICS_STALE, None,
                        (round(now - self.monitor.workers[i].timestamp, 6),))
            self.monitor.update_worker(i, queue_depth=self.queue_depth(i),
                                       touch=False)
        extra = {}
        if self.prefix_probe is not None and self._router_prefix_aware:
            extra["prefix_scores"] = {i: self.prefix_probe(i, req) for i in healthy}
        if self.slo_routing and self._router_slo_aware:
            delays = {i: self.queue_delay(i) for i in healthy}
            worker, _ = self.router.select(
                self.monitor.snapshot(), now, healthy,
                request=req, queue_delays=delays, **extra,
            )
        else:
            worker, _ = self.router.select(
                self.monitor.snapshot(), now, healthy, **extra
            )
        req.worker_id = worker
        req.state = RequestState.QUEUED
        # stamp only unset arrivals — an explicit t=0 arrival is legitimate
        if req.arrival_time is None:
            req.arrival_time = now
        self.prefill_queues[worker].append(req)
        self.routing_log.append((req.request_id, worker))
        if tr.enabled:
            bd = getattr(self.router, "last_breakdown", None)
            breakdown = tuple(
                (i, *terms) for i, terms in sorted(bd.items())
            ) if bd else ()
            tr.emit(now, -1, EV_ROUTE, req.request_id, (worker, breakdown))
            tr.emit(now, worker, EV_ENQUEUE, req.request_id,
                    (len(self.prefill_queues[worker]),))
        return worker

    def next_for_prefill(self, worker_id: int, now: Optional[float] = None) -> Optional[Request]:
        """Pop the next request to prefill.

        FIFO without SLO routing; with it, earliest-TTFT-deadline-first, and
        requests that can no longer make their deadline are shed on the way
        (the admission guard) rather than occupying a prefill slot.
        """
        q = self.prefill_queues[worker_id]
        while q:
            if not self.slo_routing:
                return q.popleft()
            idx = min(range(len(q)), key=lambda i: edf_deadline(q[i]))
            req = q[idx]
            del q[idx]
            if self.trace.enabled and idx != 0:
                # EDF reorder: the pop jumped the FIFO head
                self.trace.emit(now if now is not None else 0.0, worker_id,
                                EV_EDF_POP, req.request_id,
                                (idx, edf_deadline(req)))
            # slack already negative: the deadline passed while queued, so
            # even immediate service (this very tick) can only miss
            if now is not None and req.slo_ttft is not None and now > edf_deadline(req):
                self._shed(req, now)
                continue
            return req
        return None

    def fail_request(self, req: Request, now: float, reason: str,
                     slo_infeasible: bool = False) -> None:
        """Terminal failure with a RequestRecord — a request must never
        vanish without a record, whatever path killed it."""
        req.state = RequestState.FAILED
        req.error = reason
        req.t_end = now
        queued, prefill, decode, stall = request_phases(req)
        self.monitor.complete_request(
            RequestRecord(
                request_id=req.request_id,
                # `is not None`: an explicit tick-0 arrival is a real stamp
                t_start=req.arrival_time if req.arrival_time is not None else 0.0,
                t_end=now,
                prompt_len=req.prompt_len,
                generated=len(req.output_tokens),
                token_times=list(req.token_times),
                worker_id=req.worker_id,
                slo_ttft=req.slo_ttft,
                slo_tpot=req.slo_tpot,
                slo_infeasible=slo_infeasible,
                kv_requeued=getattr(req, "kv_requeued", 0),
                phase_queued=queued,
                phase_prefill=prefill,
                phase_decode=decode,
                phase_stall=stall,
            )
        )
        if self.trace.enabled:
            self.trace.emit(now, req.worker_id, EV_FAIL, req.request_id,
                            (reason, queued, prefill, decode, stall))

    def _shed(self, req: Request, now: float) -> None:
        """Admission guard: fail an SLO-infeasible request terminally."""
        self.shed.append(req)
        if self.trace.enabled:
            self.trace.emit(now, req.worker_id, EV_SHED, req.request_id,
                            (edf_deadline(req),))
        self.fail_request(req, now, "slo_infeasible", slo_infeasible=True)

    def queue_depth(self, worker_id: int) -> int:
        """Queued requests plus any parked mid-chunked-prefill on the pair."""
        depth = len(self.prefill_queues[worker_id])
        if self.inflight_depth is not None:
            depth += self.inflight_depth(worker_id)
        return depth

    def cancel(self, request_id: str) -> Optional[Request]:
        """Drop a still-queued request.  Returns it, or None if not queued."""
        for q in self.prefill_queues.values():
            for req in q:
                if req.request_id == request_id:
                    q.remove(req)
                    return req
        return None

    # ---------------------------------------------------------- fault handling
    def resubmit_or_fail(self, req: Request, now: float) -> bool:
        """Re-route an orphaned request, or — when no healthy worker remains
        to take it — FAIL it terminally with a RequestRecord.  ``submit()``
        raising mid-loop used to drop the remaining orphans silently."""
        if any(self.healthy.values()):
            self.submit(req, now)
            return True
        self.fail_request(req, now, "no_healthy_workers")
        return False

    def mark_unhealthy(self, worker_id: int, now: float) -> int:
        """Worker died / is draining: exclude from routing and re-route its
        queued requests (FAILED with ``error="no_healthy_workers"`` when it
        was the last worker).  Returns how many requests were re-routed."""
        self.healthy[worker_id] = False
        orphans = list(self.prefill_queues[worker_id])
        self.prefill_queues[worker_id].clear()
        rerouted = 0
        for req in orphans:
            rerouted += self.resubmit_or_fail(req, now)
        return rerouted

    def mark_healthy(self, worker_id: int) -> None:
        self.healthy[worker_id] = True

    def pending_total(self) -> int:
        return sum(len(q) for q in self.prefill_queues.values())
