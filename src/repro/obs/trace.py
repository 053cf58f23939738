"""StreamTrace — low-overhead structured event tracing for the serving stack.

The recorder is a preallocated per-worker ring of typed tuple events: no dict
churn on the hot path, no device syncs (every payload field is host state the
engine already holds after its single bulk ``device_get``), and timestamps are
the injected engine clock (ticks) — wall-clock enters only in the export
layer, so flowlint's FL3/FL4 gates stay clean.

Event tuples are ``(seq, tick, worker, etype, request_id, payload)``:

* ``seq``     — global monotonic sequence number (total order across workers)
* ``tick``    — engine clock at emission (1.0 per ``step()``)
* ``worker``  — stream-pair id, or -1 for control-plane (scheduler) events
* ``etype``   — int code from the ``EV_*`` constants (``EVENT_NAMES[etype]``)
* ``request_id`` — the subject request, or None for worker-scoped events
* ``payload`` — a flat tuple whose schema is fixed per event type (see
  ``EVENT_SCHEMAS`` and the README "Observability" table)

``TraceRecorder`` keeps the last ``capacity`` events per worker (flight-
recorder semantics: post-mortem dumps always hold each worker's recent
history even when one lane is much chattier than another).  ``NullRecorder``
is the zero-cost default: hot call sites guard payload construction with
``if trace.enabled`` so tracing off costs one attribute read per edge.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------- event codes
EV_SUBMIT = 0           # (prompt_len, slo_ttft, slo_tpot)
EV_ROUTE = 1            # (worker, ((worker, *score_terms), ...))
EV_ENQUEUE = 2          # (queue_len_after,)
EV_EDF_POP = 3          # (popped_index, deadline)
EV_SHED = 4             # (deadline,)
EV_PREFILL_START = 5    # (prompt_len, cache_hit_tokens)
EV_PREFILL_CHUNK = 6    # (cursor_after, n_tokens)
EV_PREFILL_PREEMPT = 7  # (cursor, winner_request_id)
EV_PREFILL_RESUME = 8   # (cursor,)
EV_PREFILL_END = 9      # (fused_batch,)
EV_ADMIT = 10           # (slot,)
EV_DECODE_STEP = 11     # (occupancy, k, k_pad, emitted, acceptance, depths, accepted)
EV_VERIFY = 12          # (k, k_pad)
EV_KV_ALLOC = 13        # (n_blocks, shared_blocks, hit_tokens)
EV_KV_EVICT = 14        # (slot, freed_blocks)
EV_KV_REQUEUE = 15      # (kv_requeued,)
EV_FINISH = 16          # (generated, kv_evicted, queued, prefill, decode, stalls)
EV_CANCEL = 17          # (generated, queued, prefill, decode, stalls)
EV_FAIL = 18            # (reason, queued, prefill, decode, stalls)
EV_COUNTERS = 19        # (queue_depth, free_pages, used_pages, acceptance, load, mean_depth)
EV_METRICS_STALE = 20   # (age_ticks,)
EV_WORKER_FAIL = 21     # (rerouted,)

EVENT_NAMES: Tuple[str, ...] = (
    "submit", "route", "enqueue", "edf_pop", "shed",
    "prefill_start", "prefill_chunk", "prefill_preempt", "prefill_resume",
    "prefill_end", "admit", "decode_step", "verify",
    "kv_alloc", "kv_evict", "kv_requeue",
    "finish", "cancel", "fail",
    "counters", "metrics_stale", "worker_fail",
)

# payload field names per event type — documentation + traceview rendering
EVENT_SCHEMAS: Dict[str, Tuple[str, ...]] = {
    "submit": ("prompt_len", "slo_ttft", "slo_tpot"),
    "route": ("worker", "score_breakdown"),
    "enqueue": ("queue_len",),
    "edf_pop": ("popped_index", "deadline"),
    "shed": ("deadline",),
    "prefill_start": ("prompt_len", "cache_hit_tokens"),
    "prefill_chunk": ("cursor", "n_tokens"),
    "prefill_preempt": ("cursor", "winner"),
    "prefill_resume": ("cursor",),
    "prefill_end": ("fused_batch",),
    "admit": ("slot",),
    "decode_step": ("occupancy", "k", "k_pad", "emitted", "acceptance",
                    "depths", "accepted"),
    "verify": ("k", "k_pad"),
    "kv_alloc": ("n_blocks", "shared_blocks", "hit_tokens"),
    "kv_evict": ("slot", "freed_blocks"),
    "kv_requeue": ("kv_requeued",),
    "finish": ("generated", "kv_evicted", "queued", "prefill", "decode", "stalls"),
    "cancel": ("generated", "queued", "prefill", "decode", "stalls"),
    "fail": ("reason", "queued", "prefill", "decode", "stalls"),
    "counters": ("queue_depth", "free_pages", "used_pages", "acceptance",
                 "load", "mean_depth"),
    "metrics_stale": ("age_ticks",),
    "worker_fail": ("rerouted",),
}

SCHEMA_VERSION = "streamtrace/v1"

# terminal event codes — traceview and the span assembler key off these
TERMINAL_EVENTS = (EV_FINISH, EV_CANCEL, EV_FAIL)

# ---------------------------------------------------------------- host spans
# Phase spans of the program (``jax.profiler.TraceAnnotation``): they land on
# a profiler session's host plane, on the same clock as the device ops, so a
# trace shows what the host did while the chip sat idle.  Always on: with no
# profiler session a span costs about a microsecond.  No span is per slot or
# per token; every span but ``ss.submit`` lies inside an ``ss.step``.
SPAN_SUBMIT = "ss.submit"      # StreamScheduler.submit: route + enqueue
SPAN_STEP = "ss.step"          # one engine tick (PipeServeEngine._step)
SPAN_ADMIT = "ss.admit"        # per pair: queue pops, KV reserve, prefill admission
SPAN_SPEC = "ss.spec"          # speculation policy, per-row depths, depth clamps
SPAN_DRAFT = "ss.draft"        # draft proposal (verify steps only)
SPAN_DISPATCH = "ss.dispatch"  # building inputs and enqueueing device programs
SPAN_SYNC = "ss.sync"          # host blocked in a device_get
SPAN_EMIT = "ss.emit"          # per-slot token bookkeeping, finishes, frees
SPAN_PUBLISH = "ss.publish"    # token accounting + metric publication

SPAN_NAMES: Tuple[str, ...] = (
    SPAN_SUBMIT, SPAN_STEP, SPAN_ADMIT, SPAN_SPEC, SPAN_DRAFT,
    SPAN_DISPATCH, SPAN_SYNC, SPAN_EMIT, SPAN_PUBLISH,
)


class NullRecorder:
    """Zero-cost stand-in when tracing is off (the default).

    ``enabled`` is False so hot call sites skip payload construction
    entirely; ``emit`` is still callable for call sites that don't guard.
    """

    enabled = False
    dropped = 0

    def emit(self, tick: float, worker: int, etype: int,
             request_id: Optional[str] = None, payload: Tuple = ()) -> None:
        pass

    def events(self) -> List[Tuple]:
        return []

    def to_dump(self, reason: str = "", tick: float = 0.0) -> Dict[str, Any]:
        return {"schema": SCHEMA_VERSION, "reason": reason, "tick": tick,
                "dropped": 0, "events": []}


class TraceRecorder:
    """Preallocated per-worker ring buffer of typed tuple events.

    Each worker id (lazily) owns a fixed ``capacity``-long list used as a
    circular buffer — the flight-recorder property: the dump always holds
    each worker's last ``capacity`` events, however lopsided the traffic.
    A global ``seq`` counter gives a total order for cross-worker merges.
    """

    enabled = True

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"trace capacity must be >= 1 (got {capacity})")
        self.capacity = capacity
        self._rings: Dict[int, List[Optional[Tuple]]] = {}
        self._cursor: Dict[int, int] = {}
        self._seq = 0
        self.dropped = 0  # events overwritten by ring wraparound

    def emit(self, tick: float, worker: int, etype: int,
             request_id: Optional[str] = None, payload: Tuple = ()) -> None:
        ring = self._rings.get(worker)
        if ring is None:
            ring = self._rings[worker] = [None] * self.capacity
            self._cursor[worker] = 0
        i = self._cursor[worker]
        if ring[i] is not None:
            self.dropped += 1
        ring[i] = (self._seq, tick, worker, etype, request_id, payload)
        self._cursor[worker] = (i + 1) % self.capacity
        self._seq += 1

    def __len__(self) -> int:
        return sum(
            sum(1 for e in ring if e is not None) for ring in self._rings.values()
        )

    def events(self) -> List[Tuple]:
        """All retained events merged across workers, in emission order."""
        out: List[Tuple] = []
        for ring in self._rings.values():  # dict insertion order: deterministic
            out.extend(e for e in ring if e is not None)
        out.sort(key=lambda e: e[0])
        return out

    def events_for(self, request_id: str) -> List[Tuple]:
        return [e for e in self.events() if e[4] == request_id]

    def clear(self) -> None:
        self._rings.clear()
        self._cursor.clear()
        self.dropped = 0

    # ------------------------------------------------------------------ dump
    def to_dump(self, reason: str = "", tick: float = 0.0) -> Dict[str, Any]:
        """JSON-serializable flight-recorder dump (tick timestamps only)."""
        return {
            "schema": SCHEMA_VERSION,
            "reason": reason,
            "tick": tick,
            "dropped": self.dropped,
            "columns": ["seq", "tick", "worker", "type", "request", "data"],
            "events": [
                [seq, tick_, worker, EVENT_NAMES[etype], rid, list(payload)]
                for seq, tick_, worker, etype, rid, payload in self.events()
            ],
        }


def make_recorder(mode: str, capacity: int = 4096):
    """Recorder factory for the ``trace`` config knob."""
    if mode == "off":
        return NullRecorder()
    if mode in ("on", "flight"):
        return TraceRecorder(capacity)
    raise ValueError(f"trace must be 'off', 'on' or 'flight' (got {mode!r})")
