"""PipeServe-Engine — disaggregated prefill/decode execution (paper §3.4,
Alg 1 & 3), real JAX execution path.

One :class:`StreamPair` = a prefill lane + a decode lane (on TPU: two
submeshes linked by ICI resharding — the NIXL analogue; on this CPU container
both lanes share the device and the transfer is the jitted ``insert`` below).
The decode lane runs continuous batching over ``max_batch`` slots with
SpecuStream-governed speculative flows.

Hot-path shape discipline (zero steady-state retraces):

* **Bucketed prefill** — prompts are right-padded to power-of-two length
  buckets and queued admissions are fused into one prefill call per tick
  (batch dimension bucketed too), so XLA compiles O(#buckets) prefill
  programs instead of one per distinct prompt length.
* **Depth-bucketed verify** — SpecuStream may pick any depth d; the draft is
  padded to the smallest ``verify_buckets`` member >= d and the padding is
  masked inside ``verify_tokens``, so adaptive depth never changes a traced
  shape.
* **Donated device-resident state** — the batched decode cache is donated
  through decode/commit/insert and aliased input to output.  Decode writes
  each layer's new KV rows into the stacked cache in place (the KV rides in
  the model's layer-scan carry: no whole-cache copy, no per-layer
  write-back); what is still copied per step is each layer's slice that the
  decode kernel reads (transposed head-major for the dense kernel).
  ``pending`` next-tokens live on device; ``admit`` and ``decode_iteration``
  each perform a single bulk ``jax.device_get`` for host bookkeeping.
  Donation invariant: callers must rebind ``lane.cache`` and never hold a
  reference into a donated cache (``commit`` recovers the pre-step length
  *inside* the jit for exactly this reason).

``PipeServeEngine.warmup()`` pre-compiles every bucket combination;
``jit_cache_sizes()`` exposes compiled-trace counts so benchmarks and tests
can assert the steady state stays retrace-free.

The engine is single-controller and fully deterministic given the request
trace — which is what makes the control plane property-testable.
"""
from __future__ import annotations

import dataclasses
import functools
from time import perf_counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.registry import (
    register_draft,
    resolve_draft,
    resolve_router,
    resolve_spec_policy,
)
from repro.configs.base import ArchConfig
from repro.core.metrics import PerformanceMonitor, RequestRecord
from repro.core.scheduler import StreamScheduler, edf_deadline
from repro.core.specustream import (
    VERIFY_BUCKETS,
    SlotSignals,
    SpecDecision,
    pad_to_bucket,
)
from repro.models import build_model
from repro.models.attention import SPEC_MARGIN, cache_capacity
from repro.obs.counters import WorkCounters
from repro.obs.spans import request_phases
from repro.obs.trace import (
    EV_ADMIT,
    EV_CANCEL,
    EV_COUNTERS,
    EV_DECODE_STEP,
    EV_FINISH,
    EV_KV_ALLOC,
    EV_KV_EVICT,
    EV_KV_REQUEUE,
    EV_PREFILL_CHUNK,
    EV_PREFILL_END,
    EV_PREFILL_PREEMPT,
    EV_PREFILL_RESUME,
    EV_PREFILL_START,
    EV_VERIFY,
    EV_WORKER_FAIL,
    SPAN_ADMIT,
    SPAN_DISPATCH,
    SPAN_DRAFT,
    SPAN_EMIT,
    SPAN_PUBLISH,
    SPAN_SPEC,
    SPAN_STEP,
    SPAN_SYNC,
    NullRecorder,
    make_recorder,
)
from repro.serving.cost_model import PrefillDelayEstimator
from repro.serving.draft import DraftContext, EngineDraft
from repro.serving.kv_cache import KVCacheManager
from repro.serving.request import Request, RequestState
from repro.serving.sampling import sample, sample_probs
from repro.serving.speculative import verify_tokens


@functools.partial(jax.jit, donate_argnums=(0,))
def _tree_insert_rows(big, small, slots: jax.Array):
    """Insert rows of a prefill cache into decode slots (donated in place).

    Row ``r`` of ``small`` lands in slot ``slots[r]`` of ``big``; out-of-range
    slot ids (padded admission rows) are dropped.  Batched cache leaves are
    (n_blocks, B, ...) under "blocks" and (B,) at the top level.  Jitted once
    at module level so N lanes (and draft mirrors) share compiled inserts per
    shape instead of re-jitting per ``ModelLane``.
    """

    def ins(b, s):
        if b.ndim >= 2 and s.ndim == b.ndim:  # (n_blocks, B, ...) leaves
            return b.at[:, slots].set(s.astype(b.dtype), mode="drop")
        return b.at[slots].set(s.astype(b.dtype), mode="drop")  # (B,) leaves

    return jax.tree.map(ins, big, small)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _paged_admit_step(chunk_prefill, params, cache, bt, tokens, lens, n_new):
    """Bucketed paged admission: prefill suffixes straight into the page pool.

    ``chunk_prefill`` (static — the lane model's bound step) ingests row ``b``'s
    ``n_new[b]`` suffix tokens at cursor ``lens[b]``; with the row's block
    table installed first, the KV lands directly in the decode lane's global
    page pool — admission IS the transfer, there is no separate insert.  Rows
    with a resident prefix start at ``lens = hit_tokens`` and skip recomputing
    the shared pages entirely; idle occupied rows ride along with ``n_new = 0``
    (their padding writes land past the committed length, positionally
    shadowed until real decode tokens overwrite them).  Returns each row's
    last-suffix-token logits for first-token sampling.
    """
    cache = dict(cache, bt=bt)
    logits, cache = chunk_prefill(params, cache, tokens, lens, n_new)
    S = logits.shape[1]
    idx = jnp.clip(n_new - 1, 0, S - 1)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return last, cache


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _lane_decode(decode_step, params, cache, tokens):
    """One decode step over the donated lane cache.

    ``decode_step`` (static — the lane model's closure) keys the jit cache,
    so the wrapper lives at module level: N lanes share ONE jit object whose
    cache holds one entry per (model, shape) instead of compiling a fresh
    wrapper per :class:`ModelLane` (the old FL102 per-instance-jit pattern).
    """
    return decode_step(params, cache, tokens)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _lane_commit(commit_cache, cache, n_new, accept_idx):
    """Speculative rollback of the donated lane cache.

    The pre-step length is recovered INSIDE the jit so callers never hold a
    reference into a donated cache (it would be a deleted buffer).
    """
    old_len = cache["len"] - n_new
    return commit_cache(cache, old_len, accept_idx)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,),
                   keep_unused=True)
def _lane_reset(init_cache, sizes, cache):
    """An empty lane cache written into the donated buffers of ``cache``.

    A fresh allocation would sit beside the old cache, whose buffers stay
    alive until the steps queued on them finish: at warm-up's end that put
    a second lane cache on the device and set the process's peak HBM.
    ``keep_unused``: the old cache is read by nothing, and a pruned argument
    donates nothing.
    """
    del cache
    return init_cache(*sizes)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _lane_prefill(prefill, params, max_len, batch):
    """Bucketed one-shot prefill (static model closure + max_len)."""
    return prefill(params, batch, max_len=max_len)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _chunk_step(chunk_prefill, cache, params, tokens, lens, n_new, row, last_idx):
    """One fixed-size chunked-prefill step + last-token logit gather.

    ``chunk_prefill`` (static) ingests row ``row``'s ``n_new`` suffix tokens;
    the in-jit dynamic slice pulls that row's last real logit so the caller
    samples without a second device round-trip.
    """
    logits, cache = chunk_prefill(params, cache, tokens, lens, n_new)
    last = jax.lax.dynamic_slice(
        logits, (row, last_idx, 0), (1, 1, logits.shape[-1])
    )[:, 0]
    return last, cache


@functools.partial(jax.jit, donate_argnums=(0,))
def _cache_set_bt(cache, bt):
    """Install the host-assembled block tables into the donated decode cache
    (the per-tick page-table sync; everything else is untouched aliasing)."""
    return dict(cache, bt=bt)


@functools.partial(jax.jit, donate_argnums=(0,))
def _tree_insert_pages(cache, chunk_blocks, row, page_ids, slot, seq_len):
    """Move one completed chunked-prefill row into the paged decode pool.

    The dense chunk row (contiguous positions ``[0, L)``) is reshaped into
    ``L / page_size`` pages and scattered to ``page_ids`` in every layer's
    global pool (sentinel ids — the pool size — drop pages past the prompt);
    ``cache["len"][slot]`` is seeded with the committed length.  Block tables
    are host state and sync separately via :func:`_cache_set_bt`.
    """
    blocks = {}
    for name in cache["blocks"]:
        layer = dict(cache["blocks"][name])
        for kv in ("k", "v"):
            pool = layer[kv]                      # (nb, n_pages, K, ps, D)
            src = chunk_blocks[name][kv]          # (nb, R, L, K, D)
            nb, _, Kh, ps, D = pool.shape
            rowdat = jax.lax.dynamic_index_in_dim(src, row, axis=1, keepdims=False)
            pages = rowdat.reshape(nb, -1, ps, Kh, D).transpose(0, 1, 3, 2, 4)
            layer[kv] = pool.at[:, page_ids].set(
                pages.astype(pool.dtype), mode="drop"
            )
        blocks[name] = layer
    new = dict(cache, blocks=blocks)
    new["len"] = cache["len"].at[slot].set(seq_len.astype(jnp.int32), mode="drop")
    return new


def _terminal_record(req: Request, now: float, kv_evicted: bool = False,
                     cancelled: bool = False) -> RequestRecord:
    """Terminal RequestRecord (finish, cancel, either path) with SLO fields.

    ``req.worker_id`` is stamped at submission, so records are pair-agnostic
    — queued-but-never-prefilled cancels build the same record as finishes.
    """
    depths = req.spec_depths
    queued, prefill, decode, stall = request_phases(req)
    return RequestRecord(
        request_id=req.request_id,
        t_start=req.arrival_time,
        t_end=now,
        prompt_len=req.prompt_len,
        generated=len(req.output_tokens),
        token_times=list(req.token_times),
        worker_id=req.worker_id,
        kv_evicted=kv_evicted,
        kv_requeued=req.kv_requeued,
        slo_ttft=req.slo_ttft,
        slo_tpot=req.slo_tpot,
        cancelled=cancelled,
        mean_depth=sum(depths) / len(depths) if depths else 0.0,
        phase_queued=queued,
        phase_prefill=prefill,
        phase_decode=decode,
        phase_stall=stall,
    )


def _pow2_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    """Power-of-two shape buckets from ``lo`` up to (and including) ``hi``."""
    out: List[int] = []
    b = max(lo, 1)
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


class ModelLane:
    """A model + per-slot batched decode cache + jitted step helpers.

    The cache is donated through every jitted step: ``decode``/``commit``/
    ``insert_rows`` consume the previous cache buffers and return them
    updated, aliased.  ``decode`` scatters only the T new KV rows per layer
    into the stacked cache (no whole-cache copy, no whole-layer write-back);
    the attention kernel still reads a per-layer slice copied out of it.
    ``reset_cache`` writes the empty cache into the donated old one.
    Callers must treat ``self.cache`` as the only live handle.
    """

    def __init__(self, cfg: ArchConfig, params, max_batch: int, max_len: int,
                 *, paged: bool = False, kv_blocks: int = 0,
                 kv_block_size: int = 16, max_context: Optional[int] = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.paged = paged
        self.kv_blocks = kv_blocks
        self.kv_block_size = kv_block_size
        self.max_context = (max_context or max_len) if paged else max_len
        init, sizes = self._cache_init()
        self.cache = init(*sizes)

    def _cache_init(self):
        """(init function, its arguments) of this lane's empty cache."""
        if self.paged:
            return self.model.init_paged_cache, (
                self.max_batch, self.kv_blocks, self.kv_block_size,
                self.max_context,
            )
        return self.model.init_cache, (self.max_batch, self.max_len)

    def prefill(self, batch: Dict[str, Any]):
        return _lane_prefill(self.model.prefill, self.params, self.max_len, batch)

    def insert_rows(self, slots: jax.Array, small_cache) -> None:
        """Transfer prefill rows into decode slots (row r -> slots[r])."""
        self.cache = _tree_insert_rows(self.cache, small_cache, slots)

    def decode(self, tokens: jax.Array):
        logits, self.cache = _lane_decode(
            self.model.decode_step, self.params, self.cache, tokens
        )
        return logits

    def commit(self, n_new: int, accept_idx: jax.Array) -> None:
        """Roll back the last ``n_new`` ingested tokens to ``accept_idx``."""
        self.cache = _lane_commit(
            self.model.commit_cache, self.cache, n_new, accept_idx
        )

    def reset_cache(self) -> None:
        self.cache = _lane_reset(*self._cache_init(), self.cache)

    @property
    def lengths(self) -> jax.Array:
        return self.cache["len"]


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_len: int = 512
    temperature: float = 0.0
    kv_blocks: int = 4096
    kv_block_size: int = 16
    draft: str = "ngram"            # any name in repro.api.DRAFTS
    max_ngram: int = 4
    adaptive: bool = True            # SpecuStream on (False => fixed depth)
    fixed_depth: int = 5
    spec_config: Any = None
    # registry names; spec_policy=None derives from the legacy `adaptive` flag
    router: str = "flowguard"        # any name in repro.api.ROUTERS
    router_config: Any = None
    spec_policy: Optional[str] = None  # any name in repro.api.SPEC_POLICIES
    # hot-path shape bucketing (disable both for the seed-identical
    # retrace-per-shape path, e.g. as a benchmark baseline)
    prefill_buckets: bool = True     # pow2 prompt-length buckets + fused admits
    prefill_bucket_min: int = 16     # smallest prompt-length bucket
    admit_batch: int = 4             # max admissions fused into one prefill call
    verify_buckets: Optional[Tuple[int, ...]] = VERIFY_BUCKETS
    # chunked prefill: prompts are ingested in fixed-size chunks through ONE
    # compiled prefill step (vs one trace per pow2 bucket), and the chunk
    # boundary is a preemption point — an earlier-deadline arrival can park a
    # partially-prefilled long prompt.  None = one-shot (bucketed) prefill.
    prefill_chunk: Optional[int] = None
    prefill_preempt: bool = True     # EDF preemption at chunk boundaries
    # ---- SLO control plane -------------------------------------------------
    # per-row speculation depths: each decode slot independently picks a depth
    # from its own acceptance EMA + TPOT headroom (needs verify_buckets — the
    # shared bucket >= max row depth keeps traced shapes fixed)
    per_row_depth: bool = True
    # SLO-aware routing: FlowGuard TTFT-slack scoring, EDF prefill ordering,
    # and the shed-on-negative-slack admission guard
    slo_routing: bool = True
    # ---- paged KV + radix prefix reuse -------------------------------------
    # paged_kv=True replaces the per-slot dense (max_batch, max_len) KV cache
    # with a global page pool (kv_blocks pages of kv_block_size tokens) plus
    # per-row block tables: sequences grow lazily page-by-page (continuous
    # batching under real memory pressure), context may exceed max_len up to
    # max_context, and resident prefix pages are shared copy-on-write across
    # requests (radix prefix cache — repeated prompts skip prefill).
    paged_kv: bool = False
    max_context: Optional[int] = None  # per-sequence token ceiling; None = max_len
    # mid-decode pool exhaustion: "requeue" evicts the lowest-priority victim's
    # pages and resubmits it (it restarts from scratch, recorded via
    # kv_requeued); "truncate" is the pre-paging behaviour — finish the starved
    # sequence early with kv_evicted=True
    kv_evict_policy: str = "requeue"
    # ---- StreamTrace observability -----------------------------------------
    # "off" (zero-cost no-op recorder), "on" (full tracing + exporters), or
    # "flight" (tracing whose primary consumer is the post-mortem dump).  Any
    # enabled mode dumps the ring on engine exception / fail_worker.
    trace: str = "off"
    trace_capacity: int = 4096       # retained events per worker (ring size)
    trace_dir: Optional[str] = None  # also write flight dumps here as JSON

    def resolved_spec_policy(self) -> str:
        if self.spec_policy is not None:
            return self.spec_policy
        return "specustream" if self.adaptive else "fixed"


class StreamPair:
    """One disaggregated prefill+decode lane pair (paper Alg 3)."""

    def __init__(
        self,
        worker_id: int,
        cfg: ArchConfig,
        params,
        econf: EngineConfig,
        monitor: PerformanceMonitor,
        draft_cfg: Optional[ArchConfig] = None,
        draft_params=None,
        trace=None,
    ):
        self.worker_id = worker_id
        self.econf = econf
        self.monitor = monitor
        self.trace = trace if trace is not None else NullRecorder()
        # length bucketing / chunking need padding (resp. cursor-offset
        # continuation) to be invisible, which holds for causal attention but
        # not for SSM state / enc-dec / frontends
        arch_ok = (
            not cfg.is_encdec
            and cfg.frontend is None
            and all(kind == "attn" for kind in cfg.layer_kinds())
        )
        # ---- paged KV gating ---------------------------------------------
        # Paged decode shares the chunked-prefill position discipline (offset
        # cursors, positional shadowing), so it inherits the same arch gate;
        # sliding windows would additionally need ring-evicted pages, which
        # the write-once page layout deliberately does not model.
        self._paged = bool(econf.paged_kv)
        if self._paged:
            if not arch_ok or cfg.sliding_window is not None:
                raise ValueError(
                    "paged_kv requires an attention-only decoder without a "
                    "sliding window (no enc-dec / SSM / frontend)"
                )
            if econf.max_len % econf.kv_block_size:
                raise ValueError(
                    f"paged_kv requires kv_block_size "
                    f"({econf.kv_block_size}) to divide max_len "
                    f"({econf.max_len}) — chunked rows insert whole pages"
                )
            if econf.max_context is not None and econf.max_context < econf.max_len:
                raise ValueError(
                    f"max_context ({econf.max_context}) must be >= max_len "
                    f"({econf.max_len})"
                )
            if econf.kv_evict_policy not in ("requeue", "truncate"):
                raise ValueError(
                    f"kv_evict_policy must be 'requeue' or 'truncate' "
                    f"(got {econf.kv_evict_policy!r})"
                )
        vb = econf.verify_buckets
        # page headroom every row keeps ahead of its committed length: the
        # deepest verify step writes bucket+1 tokens before the host can
        # extend, and writes past a row's block table are silently dropped
        self._kv_margin = (vb[-1] + 1) if vb else 9
        self._max_context = (econf.max_context or econf.max_len) if self._paged \
            else econf.max_len
        self._pages_max = -(-self._max_context // econf.kv_block_size)
        self.lane = ModelLane(
            cfg, params, econf.max_batch, econf.max_len,
            paged=self._paged, kv_blocks=econf.kv_blocks,
            kv_block_size=econf.kv_block_size, max_context=self._max_context,
        )
        self.kv = KVCacheManager(
            econf.kv_blocks, econf.kv_block_size,
            serve_prefixes=self._paged,
            max_seq_blocks=self._pages_max if self._paged else None,
        )
        # host mirror of the device block tables: admission/extension edit it,
        # _sync_bt() pushes it once per decode tick when dirty
        self._bt_host = np.full(
            (econf.max_batch, self._pages_max), -1, np.int32
        )
        self._bt_dirty = False
        # eviction→requeue callback (wired by PipeServeEngine to the
        # scheduler's resubmit_or_fail); None falls back to truncate
        self.requeue = None
        self.spec = resolve_spec_policy(
            econf.resolved_spec_policy(),
            config=econf.spec_config,
            fixed_depth=econf.fixed_depth,
        )
        self.draft: EngineDraft = resolve_draft(
            econf.draft,
            DraftContext(cfg=cfg, econf=econf, draft_cfg=draft_cfg, draft_params=draft_params),
        )
        if self._paged and type(self.draft).on_admit is not EngineDraft.on_admit:
            raise ValueError(
                "paged_kv is incompatible with drafts that mirror admission "
                "state (draft='model'); use 'ngram'/'none' or disable paging"
            )
        self._bucketed = econf.prefill_buckets and arch_ok
        self._len_buckets = _pow2_buckets(
            econf.prefill_bucket_min, self._max_context
        )
        self._admit_buckets = _pow2_buckets(1, max(econf.admit_batch, 1))
        # ---- chunked prefill --------------------------------------------------
        # One (R, C) chunk step — jitted once — replaces the whole bucket
        # family; per-request cursors live on the host and a chunk row parks
        # between chunks, which is what makes prefill preemptible.
        self._chunk: Optional[int] = None
        if econf.prefill_chunk and arch_ok:
            if type(self.draft).on_admit is not EngineDraft.on_admit:
                raise ValueError(
                    "prefill_chunk is incompatible with drafts that mirror "
                    "admission state (draft='model'); use 'ngram'/'none' or "
                    "disable chunking"
                )
            # Chunk-size safety clamps.  Every chunk step writes C positions
            # starting at a multiple of C (real tokens and the rewound padding
            # of partial/idle rows alike), so C must divide the cache capacity
            # or the final window wraps the ring and clobbers the prompt head
            # with padding stamped at wrapped positions.  Sliding-window
            # caches additionally bound the write burst by SPEC_MARGIN — the
            # ring slack that keeps in-step writes from evicting positions
            # still inside the earliest query's attention window (the same
            # guarantee speculative decoding relies on).
            cap = cache_capacity(cfg, econf.max_len)
            C = min(econf.prefill_chunk, cap)
            if cfg.sliding_window is not None:
                C = min(C, SPEC_MARGIN)
            while cap % C:
                C -= 1
            self._chunk = C
            n_rows = max(econf.admit_batch, 2)  # >= 2: one parked + one active
            self.chunk_rows: List[Optional[Request]] = [None] * n_rows
            self.chunk_cursor: Dict[str, int] = {}
            # last request granted a chunk — preempt/resume trace detection
            self._chunk_last: Optional[str] = None
            self.chunk_cache = self.lane.model.init_cache(n_rows, econf.max_len)
        # slot state -----------------------------------------------------------
        self.slot_req: List[Optional[Request]] = [None] * econf.max_batch
        # device-resident pending next-token per slot (sampled, not ingested)
        self.pending = jnp.zeros((econf.max_batch,), jnp.int32)
        self.histories: List[List[int]] = [[] for _ in range(econf.max_batch)]
        self.acceptance = 0.7  # optimistic prior
        self.key = jax.random.PRNGKey(worker_id)
        self.healthy = True
        self.counters = WorkCounters()

    # --------------------------------------------------------------- helpers
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def prefill_in_flight(self) -> int:
        """Requests parked or active in chunk rows (0 when chunking is off)."""
        if self._chunk is None:
            return 0
        return sum(1 for r in self.chunk_rows if r is not None)

    def active_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    @property
    def load(self) -> float:
        return len(self.active_slots()) / self.econf.max_batch

    def admit_cap(self) -> int:
        """How many admissions may fuse into one prefill call."""
        return max(self.econf.admit_batch, 1) if self._bucketed else 1

    @staticmethod
    def _bucket(n: int, buckets: Tuple[int, ...]) -> int:
        for b in buckets:
            if b >= n:
                return b
        return n  # oversize (prompt > max_len): correctness over shape reuse

    def _spec_reset_slot(self, slot: int) -> None:
        """Drop the policy's per-slot state when a slot changes occupant."""
        reset = getattr(self.spec, "reset_slot", None)
        if reset is not None:
            reset(slot)

    def _select_row_depths(self, throughput: float) -> np.ndarray:
        """Per-row speculation depths (B,), 0 on empty slots.

        Occupied rows pick independently from the policy's per-slot
        acceptance EMA and the request's TPOT headroom (measured TPOT vs
        ``slo_tpot``); rows sharing the batch still share one verify shape
        because the engine pads to the bucket >= the max row depth.
        """
        signals: List[Optional[SlotSignals]] = []
        for req in self.slot_req:
            if req is None:
                signals.append(None)
            else:
                signals.append(SlotSignals(
                    slo_tpot=req.slo_tpot, tpot=req.measured_tpot(),
                ))
        return np.asarray(
            self.spec.select_depths(signals, self.load, throughput), np.int64
        )

    # ---------------------------------------------------------------- prefill
    def reserve_kv(self, req: Request, now: float = 0.0) -> bool:
        """Allocate KV blocks for a request ahead of its (batched) prefill.

        Dense mode reserves the worst case (prompt + max_new) up front; paged
        mode reserves only prompt + margin and grows page-by-page as the
        sequence decodes (continuous batching under real memory pressure).
        Paged chunked ingest opts out of prefix sharing (``share=False``) —
        chunk rows recompute from position 0, so resident pages cannot be
        skipped mid-row.
        """
        if self._paged:
            alloc = self.kv.allocate_sequence(
                req.request_id, list(req.prompt),
                extra_tokens=self._kv_margin, share=self._chunk is None,
            )
        else:
            alloc = self.kv.allocate_sequence(
                req.request_id, list(req.prompt),
                extra_tokens=req.params.max_new_tokens,
            )
        if alloc is None:
            return False  # KV pool exhausted — stays queued
        req.cache_hit_tokens = alloc.shared_blocks * self.kv.pool.block_size
        if self.trace.enabled:
            self.trace.emit(now, self.worker_id, EV_KV_ALLOC, req.request_id,
                            (len(alloc.block_ids), alloc.shared_blocks,
                             req.cache_hit_tokens))
        return True

    def prompt_fits(self, req: Request) -> bool:
        """Whether a request can EVER be admitted on this pair.  A prompt over
        the paged context ceiling would requeue forever at the queue head, so
        the engine fails it terminally instead."""
        if not self._paged:
            return True
        if len(req.prompt) + self._kv_margin > self._pages_max * self.econf.kv_block_size:
            return False
        if self._chunk is not None and len(req.prompt) > self.econf.max_len:
            return False  # chunk rows are max_len-sized dense staging
        return True

    def _refresh_bt_row(self, slot: int, request_id: str) -> None:
        """Mirror a sequence's current block ids into the host block table."""
        bids = self.kv.seqs[request_id].block_ids
        row = self._bt_host[slot]
        if len(bids) < row.shape[0]:
            row[len(bids):] = -1
        row[: len(bids)] = bids
        self._bt_dirty = True

    def _sync_bt(self) -> None:
        """Push the host block-table mirror to the device cache (one transfer
        per tick, only when admission/extension/eviction changed a row)."""
        if self._bt_dirty:
            self.lane.cache = _cache_set_bt(
                self.lane.cache, jnp.asarray(self._bt_host)
            )
            self._bt_dirty = False

    def admit(self, reqs: List[Request], now: float) -> None:
        """Prefill a batch of KV-reserved requests in ONE bucketed call and
        transfer their KV into free decode slots (one bulk device_get)."""
        if self._paged:
            return self._admit_paged(reqs, now)
        slots = self.free_slots()[: len(reqs)]
        assert len(slots) == len(reqs), "admit() requires a free slot per request"
        with TraceAnnotation(SPAN_DISPATCH):
            self._start_prefill(reqs, now)
            if self._bucketed:
                S = self._bucket(max(len(r.prompt) for r in reqs), self._len_buckets)
                Bb = self._bucket(len(reqs), self._admit_buckets)
                tokens = np.zeros((Bb, S), np.int32)
                lengths = np.ones((Bb,), np.int32)  # pad rows: 1 garbage token
                for i, req in enumerate(reqs):
                    tokens[i, : len(req.prompt)] = req.prompt
                    lengths[i] = len(req.prompt)
                batch = {"tokens": jnp.asarray(tokens), "lengths": jnp.asarray(lengths)}
            else:
                Bb, S = 1, len(reqs[0].prompt)  # legacy path: exact shapes, one per call
                batch = {"tokens": jnp.asarray(list(reqs[0].prompt), jnp.int32)[None, :]}
            self._count_prefill(sum(len(r.prompt) for r in reqs), Bb * S)
            slot_ids = np.full((Bb,), self.econf.max_batch, np.int32)  # OOB = dropped
            slot_ids[: len(reqs)] = slots
            slots_dev = jnp.asarray(slot_ids)
            last_logits, small_cache = self.lane.prefill(batch)
            # --- KV transfer (NIXL analogue): insert into the decode lane ----
            for req in reqs:
                req.state = RequestState.TRANSFERRING
            self.lane.insert_rows(slots_dev, small_cache)
            self.draft.on_admit(self, batch, slots_dev)
            self.key, sk = jax.random.split(self.key)
            first = sample(sk, last_logits, self.econf.temperature).astype(jnp.int32)
            self.pending = self.pending.at[slots_dev].set(first, mode="drop")
        with TraceAnnotation(SPAN_SYNC):
            first_h = np.asarray(jax.device_get(first))  # the ONE admit round-trip
        self._start_decoding(reqs, slots, first_h, now, fused=len(reqs))

    def _start_prefill(self, reqs: List[Request], now: float) -> None:
        """Requests enter prefill: state, tick and wall stamps, trace events."""
        w_start = perf_counter()
        for req in reqs:
            req.state = RequestState.PREFILLING
            req.t_prefill_start = now
            req.w_prefill_start = w_start
            if self.trace.enabled:
                self.trace.emit(now, self.worker_id, EV_PREFILL_START,
                                req.request_id,
                                (req.prompt_len, req.cache_hit_tokens))

    def _count_prefill(self, live: int, computed: int) -> None:
        """One prefill program fed ``live`` prompt tokens and computed
        ``computed`` positions (its bucket's rows x length)."""
        c = self.counters
        c.prefill_calls += 1
        c.prefill_live_tokens += live
        c.prefill_slot_tokens += computed

    def _start_decoding(self, reqs: List[Request], slots: List[int],
                        first_h: np.ndarray, now: float, fused: int) -> None:
        """Host bookkeeping once admitted requests' first tokens are on the
        host: each request takes its decode slot and its first token."""
        w_first = perf_counter()
        tr = self.trace
        with TraceAnnotation(SPAN_EMIT):
            for i, req in enumerate(reqs):
                tok = int(first_h[i])
                req.state = RequestState.DECODING
                req.t_prefill_end = now
                req.t_first_token = now
                req.w_first_token = w_first
                req.output_tokens.append(tok)
                req.token_times.append(now)
                self.slot_req[slots[i]] = req
                self.histories[slots[i]] = [*req.prompt, tok]
                self._spec_reset_slot(slots[i])  # fresh request, fresh EMA
                if tr.enabled:
                    tr.emit(now, self.worker_id, EV_PREFILL_END, req.request_id,
                            (fused,))
                    tr.emit(now, self.worker_id, EV_ADMIT, req.request_id,
                            (slots[i],))

    def _admit_paged(self, reqs: List[Request], now: float) -> None:
        """Paged admission: ONE bucketed suffix-prefill straight into pages.

        Each request's resident-prefix pages (``cache_hit_tokens``, reserved
        by ``reserve_kv``) are skipped outright — its row starts at cursor
        ``lens = hit`` and only the suffix is recomputed.  The full decode
        batch rides through the step (idle rows at their committed cursor
        with ``n_new = 0``), block tables install inside the jit, and the KV
        lands directly in the decode lane's page pool: admission and transfer
        are the same write.
        """
        slots = self.free_slots()[: len(reqs)]
        assert len(slots) == len(reqs), "admit() requires a free slot per request"
        with TraceAnnotation(SPAN_DISPATCH):
            self._start_prefill(reqs, now)
            B = self.econf.max_batch
            suffixes = [len(r.prompt) - r.cache_hit_tokens for r in reqs]
            S = self._bucket(max(suffixes), self._len_buckets)
            self._count_prefill(sum(suffixes), B * S)
            tokens = np.zeros((B, S), np.int32)
            lens = np.zeros((B,), np.int32)
            n_new = np.zeros((B,), np.int32)
            for b, occupant in enumerate(self.slot_req):
                if occupant is not None:  # idle rows hold their committed cursor
                    lens[b] = len(occupant.prompt) + len(occupant.output_tokens) - 1
            for req, slot in zip(reqs, slots):
                suffix = list(req.prompt[req.cache_hit_tokens:])
                tokens[slot, : len(suffix)] = suffix
                lens[slot] = req.cache_hit_tokens
                n_new[slot] = len(suffix)
                self._refresh_bt_row(slot, req.request_id)
            for req in reqs:
                req.state = RequestState.TRANSFERRING
            last, self.lane.cache = _paged_admit_step(
                self.lane.model.chunk_prefill, self.lane.params, self.lane.cache,
                jnp.asarray(self._bt_host), jnp.asarray(tokens),
                jnp.asarray(lens), jnp.asarray(n_new),
            )
            self._bt_dirty = False  # the admit step installed the fresh tables
            self.key, sk = jax.random.split(self.key)
            first = sample(sk, last, self.econf.temperature).astype(jnp.int32)
            slots_dev = jnp.asarray(np.asarray(slots, np.int32))
            first_rows = first[slots_dev]
            self.pending = self.pending.at[slots_dev].set(first_rows, mode="drop")
        with TraceAnnotation(SPAN_SYNC):
            first_h = np.asarray(jax.device_get(first_rows))  # the ONE admit round-trip
        self._start_decoding(reqs, slots, first_h, now, fused=len(reqs))

    # --------------------------------------------------------- chunked prefill
    def _chunk_pull(self, scheduler, now: float) -> None:
        """Admit queued requests into free chunk rows.

        A row is granted only while every in-flight chunk request can still
        claim a decode slot at completion (free slots stay strictly above the
        occupied-row count).  With preemption off the lane runs one request
        to completion before pulling the next (FIFO service); with it on,
        arrivals join rows eagerly so EDF can park in-progress work.
        """
        wid = self.worker_id
        while True:
            free_rows = [r for r, rq in enumerate(self.chunk_rows) if rq is None]
            occupied = len(self.chunk_rows) - len(free_rows)
            if not free_rows or len(self.free_slots()) <= occupied:
                return
            if not self.econf.prefill_preempt and occupied:
                return  # run-to-completion: one request in flight at a time
            req = scheduler.next_for_prefill(wid, now)
            if req is None:
                return
            if not self.prompt_fits(req):
                scheduler.fail_request(req, now, "exceeds_max_context")
                continue
            if not self.reserve_kv(req, now):
                scheduler.prefill_queues[wid].appendleft(req)
                return  # KV pool exhausted — stays queued
            req.state = RequestState.PREFILLING
            req.t_prefill_start = now
            self.chunk_rows[free_rows[0]] = req
            self.chunk_cursor[req.request_id] = 0
            if self.trace.enabled:
                self.trace.emit(now, wid, EV_PREFILL_START, req.request_id,
                                (req.prompt_len, req.cache_hit_tokens))

    def _chunk_pick_row(self) -> Optional[int]:
        """Which row gets this tick's chunk: EDF over occupied rows when
        preemption is on (ties broken by row index — deterministic), else the
        single in-flight row."""
        occ = [(r, rq) for r, rq in enumerate(self.chunk_rows) if rq is not None]
        if not occ:
            return None
        if self.econf.prefill_preempt:
            return min(occ, key=lambda t: (edf_deadline(t[1]), t[0]))[0]
        return occ[0][0]

    def chunk_tick(self, scheduler, now: float) -> None:
        """One prefill-lane tick under chunked prefill (paper's elastic
        chunk-level execution): pull arrivals, serve ONE fixed-size chunk to
        the earliest-deadline row, and complete the row into a decode slot
        when its cursor reaches the prompt end.  The chunk boundary between
        ticks is the preemption point — a tight-deadline arrival pulled by
        ``_chunk_pull`` wins the next ``_chunk_pick_row`` and the long
        prompt's partial KV parks in its row, resumed chunk-aligned."""
        self._chunk_pull(scheduler, now)
        row = self._chunk_pick_row()
        if row is None:
            return
        req = self.chunk_rows[row]
        C = self._chunk
        R = len(self.chunk_rows)
        cur = self.chunk_cursor[req.request_id]
        tr = self.trace
        if tr.enabled:
            last = self._chunk_last
            if last is not None and last != req.request_id \
                    and last in self.chunk_cursor:
                # the previous occupant of the lane still has chunks left but
                # lost this tick's grant: EDF preempted it
                tr.emit(now, self.worker_id, EV_PREFILL_PREEMPT, last,
                        (self.chunk_cursor[last], req.request_id))
            if cur > 0 and last != req.request_id:
                tr.emit(now, self.worker_id, EV_PREFILL_RESUME,
                        req.request_id, (cur,))
        self._chunk_last = req.request_id
        req.prefill_active_ticks += 1  # a lane turn actually granted
        with TraceAnnotation(SPAN_DISPATCH):
            n = min(C, len(req.prompt) - cur)
            tokens = np.zeros((R, C), np.int32)
            tokens[row, :n] = req.prompt[cur : cur + n]
            lens = np.zeros((R,), np.int32)
            for r, rq in enumerate(self.chunk_rows):
                if rq is not None:
                    lens[r] = self.chunk_cursor[rq.request_id]
            n_new = np.zeros((R,), np.int32)
            n_new[row] = n
            self._count_prefill(n, R * C)
            if cur == 0:  # the request's first chunk: its prefill starts now
                req.w_prefill_start = perf_counter()
            last_logits, self.chunk_cache = _chunk_step(
                self.lane.model.chunk_prefill, self.chunk_cache, self.lane.params,
                jnp.asarray(tokens), jnp.asarray(lens), jnp.asarray(n_new),
                np.int32(row), np.int32(max(n - 1, 0)),
            )
        cur += n
        self.chunk_cursor[req.request_id] = cur
        if tr.enabled:
            tr.emit(now, self.worker_id, EV_PREFILL_CHUNK, req.request_id,
                    (cur, n))
        if cur >= len(req.prompt):
            self._chunk_complete(row, req, last_logits, now)

    def _chunk_complete(self, row: int, req: Request, last_logits, now: float) -> None:
        """Final chunk done: transfer the row's KV into a free decode slot
        (the NIXL analogue, same drop-mode insert as batched admission) and
        sample the first token."""
        slot = self.free_slots()[0]  # guaranteed by the _chunk_pull budget
        req.state = RequestState.TRANSFERRING
        with TraceAnnotation(SPAN_DISPATCH):
            if self._paged:
                # the dense chunk row becomes whole pages in the global pool;
                # pages past the prompt keep the pool-size sentinel (dropped)
                ps = self.econf.kv_block_size
                bids = self.kv.seqs[req.request_id].block_ids
                n_pages = -(-len(req.prompt) // ps)
                page_ids = np.full((self.econf.max_len // ps,),
                                   self.kv.pool.n_blocks, np.int32)
                page_ids[:n_pages] = bids[:n_pages]
                self.lane.cache = _tree_insert_pages(
                    self.lane.cache, self.chunk_cache["blocks"], jnp.int32(row),
                    jnp.asarray(page_ids), jnp.int32(slot),
                    jnp.int32(len(req.prompt)),
                )
                self._refresh_bt_row(slot, req.request_id)
            else:
                slot_ids = np.full((len(self.chunk_rows),), self.econf.max_batch, np.int32)
                slot_ids[row] = slot
                self.lane.insert_rows(jnp.asarray(slot_ids), self.chunk_cache)
            self.key, sk = jax.random.split(self.key)
            first = sample(sk, last_logits, self.econf.temperature).astype(jnp.int32)
            self.pending = self.pending.at[jnp.asarray([slot])].set(first, mode="drop")
        with TraceAnnotation(SPAN_SYNC):
            first_h = np.asarray(jax.device_get(first))
        self.chunk_rows[row] = None
        del self.chunk_cursor[req.request_id]
        if self._chunk_last == req.request_id:
            self._chunk_last = None
        self._start_decoding([req], [slot], first_h, now, fused=1)

    def chunk_release(self, row: int) -> Request:
        """Evict a chunk row without completing it (cancel / worker failure).
        The parked KV is simply abandoned — cursors are host state and the
        stale cache slots are shadowed by the row's next occupant."""
        req = self.chunk_rows[row]
        self.chunk_rows[row] = None
        self.chunk_cursor.pop(req.request_id, None)
        if self._chunk_last == req.request_id:
            self._chunk_last = None
        self.kv.free_sequence(req.request_id)
        return req

    # ----------------------------------------------------------------- decode
    def _row_depths(self, active: List[int]) -> Tuple[np.ndarray, bool]:
        """Speculation depth of each row (B,), 0 on empty slots, clamped to
        what the draft, the verify buckets and the page margin allow; and
        whether the rows chose their depths independently."""
        B = self.econf.max_batch
        vb = self.econf.verify_buckets
        throughput = self.monitor.workers[self.worker_id].recent_throughput
        decision: SpecDecision = self.spec.adapt(
            self.acceptance, self.load, throughput,
        )
        # per-row depths need both the knob and a shared verify bucket set
        # (the bucket >= max row depth is what keeps traced shapes fixed)
        per_row = (
            self.econf.per_row_depth
            and vb is not None
            and hasattr(self.spec, "select_depths")
        )
        if per_row:
            rows = self._select_row_depths(throughput)
        else:
            rows = np.zeros((B,), np.int64)
            rows[active] = decision.bucket_depth
        rows = np.minimum(rows, self.draft.max_depth)
        if vb:
            rows = np.minimum(rows, vb[-1])
        if self._paged:
            # the deepest verify writes bucket+1 tokens before the host can
            # extend a block table — depth past the page margin would drop
            # accepted KV on the floor
            rows = np.minimum(rows, self._kv_margin - 1)
        return rows, per_row

    def decode_iteration(self, now: float) -> int:
        """One continuous-batching decode step (speculative when enabled).
        Returns number of tokens emitted across the batch."""
        active = self.active_slots()
        if not active:
            return 0
        B = self.econf.max_batch
        vb = self.econf.verify_buckets
        with TraceAnnotation(SPAN_SPEC):
            rows, per_row = self._row_depths(active)
            k = int(rows.max())
            if k:
                for s in active:
                    self.slot_req[s].spec_depths.append(int(rows[s]))
        active_mask = np.zeros((B,), bool)
        active_mask[active] = True

        if k == 0:  # plain autoregressive step
            with TraceAnnotation(SPAN_DISPATCH):
                if self._paged:
                    self._sync_bt()  # page-table edits land before any device step
                active_dev = jnp.asarray(active_mask)
                logits = self.lane.decode(self.pending[:, None])
                self.lane.commit(1, jnp.zeros((B,), jnp.int32))
                self.key, sk = jax.random.split(self.key)
                nxt = sample(sk, logits[:, 0], self.econf.temperature).astype(jnp.int32)
                self.pending = jnp.where(active_dev, nxt, self.pending)
            with TraceAnnotation(SPAN_SYNC):
                nxt_h = np.asarray(jax.device_get(nxt))  # the ONE decode round-trip
            with TraceAnnotation(SPAN_EMIT):
                self.counters.decode_calls += 1
                emitted = 0
                for s in active:
                    emitted += self._emit(s, [int(nxt_h[s])], now)
                if self.trace.enabled:
                    self.trace.emit(
                        now, self.worker_id, EV_DECODE_STEP, None,
                        (len(active), 0, 0, emitted, round(self.acceptance, 6),
                         (), ()),
                    )
            return emitted

        # ---- draft proposal (real depth k, padded to a shape bucket) --------
        with TraceAnnotation(SPAN_DRAFT):
            draft_toks, draft_q = self.draft.propose(self, k)
        with TraceAnnotation(SPAN_DISPATCH):
            if self._paged:
                self._sync_bt()  # page-table edits land before any device step
            active_dev = jnp.asarray(active_mask)
            k_pad = pad_to_bucket(k, vb)
            draft_toks = jnp.asarray(draft_toks, jnp.int32)
            draft_q = jnp.asarray(draft_q, jnp.float32)
            if k_pad > k:
                draft_toks = jnp.pad(draft_toks, ((0, 0), (0, k_pad - k)), mode="edge")
                draft_q = jnp.pad(draft_q, ((0, 0), (0, k_pad - k)), constant_values=1.0)
            if per_row:
                # heterogeneous (B,) depths: traced VALUES in the existing traced
                # shape — verify_tokens already masks per-row
                depth = jnp.asarray(rows, jnp.int32)
            else:
                depth = jnp.full((B,), k, jnp.int32) if vb else None

            # ---- target verify step (T = k_pad+1 tokens, one traced shape/bucket)
            verify_in = jnp.concatenate([self.pending[:, None], draft_toks], axis=1)
            logits = self.lane.decode(verify_in)  # (B, k_pad+1, V)
            self.key, sk = jax.random.split(self.key)
            res = verify_tokens(
                sk,
                draft_toks,
                draft_q,
                logits,
                active=active_dev,
                temperature=self.econf.temperature,
                depth=depth,
            )
            self.lane.commit(k_pad + 1, res.accept_idx)
            self.draft.on_commit(self, res.accept_idx, k)
            self.pending = jnp.where(active_dev, res.next_token.astype(jnp.int32), self.pending)
        with TraceAnnotation(SPAN_SYNC):
            # the ONE decode round-trip: everything host bookkeeping needs at once
            n_acc, nxt, draft_np = map(
                np.asarray, jax.device_get((res.n_accepted, res.next_token, draft_toks))
            )
        with TraceAnnotation(SPAN_EMIT):
            if per_row:
                # per-row acceptance: each slot's fraction of ITS OWN depth feeds
                # the policy's per-slot EMA; the pair-level EMA keeps the mean
                observe = getattr(self.spec, "observe_slot", None)
                fracs = []
                for s in active:
                    d_s = int(rows[s])
                    frac = float(n_acc[s]) / max(d_s, 1)
                    fracs.append(frac)
                    if observe is not None and d_s > 0:
                        observe(s, frac)
                accepted_frac = sum(fracs) / len(fracs)
            else:
                accepted_frac = float(n_acc[active].mean()) / max(k, 1)
            self.acceptance = 0.8 * self.acceptance + 0.2 * accepted_frac
            c = self.counters
            c.verify_calls += 1
            c.spec_proposed += int(rows[active].sum())
            c.spec_accepted += int(n_acc[active].sum())

            if self.trace.enabled:
                self.trace.emit(now, self.worker_id, EV_VERIFY, None, (k, k_pad))
            emitted = 0
            for s in active:
                toks = [*(int(t) for t in draft_np[s, : int(n_acc[s])]), int(nxt[s])]
                emitted += self._emit(s, toks, now)
            if self.trace.enabled:
                self.trace.emit(
                    now, self.worker_id, EV_DECODE_STEP, None,
                    (len(active), k, k_pad, emitted, round(self.acceptance, 6),
                     tuple(int(rows[s]) for s in active),
                     tuple(int(n_acc[s]) for s in active)),
                )
        return emitted

    def _emit(self, slot: int, tokens: List[int], now: float) -> int:
        """Host-side bookkeeping for one slot's freshly decoded tokens (the
        device values were already fetched in one bulk transfer upstream)."""
        req = self.slot_req[slot]
        if req is None:
            return 0  # evicted this very tick by an earlier slot's grant
        if self._paged:
            return self._emit_paged(slot, req, tokens, now)
        granted = self.kv.extend_up_to(req.request_id, len(tokens))
        count = 0
        for t in tokens[:granted]:
            if req.is_done():
                break
            req.output_tokens.append(t)
            req.token_times.append(now)
            self.histories[slot].append(t)
            count += 1
        # block pool ran dry mid-decode: truncate and finish gracefully
        # instead of over-committing accounting against unallocated blocks
        evicted = granted < len(tokens) and not req.is_done()
        if req.is_done() or evicted:
            self._finish(slot, now, kv_evicted=evicted)
        return count

    def _emit_paged(self, slot: int, req: Request, tokens: List[int],
                    now: float) -> int:
        """Paged emit: grant pages for the step's committed tokens, feed the
        incremental prefix hash, evict-and-requeue on pool pressure, and
        restore the page margin for the next decode step."""
        # the device committed stream trails the emitted stream by one: the
        # newest token is pending (sampled, not yet ingested), so this grant
        # covers [previous pending token, *accepted draft tokens]
        committed = [req.output_tokens[-1], *tokens[:-1]]
        need = len(tokens)
        granted = self.kv.extend_up_to(req.request_id, need, tokens=committed)
        while granted < need:
            victim = self._pick_victim(slot)
            if victim is None:
                break
            self._requeue_slot(victim, now)
            granted += self.kv.extend_up_to(
                req.request_id, need - granted, tokens=committed[granted:]
            )
        count = 0
        for t in tokens[:granted]:
            if req.is_done():
                break
            req.output_tokens.append(t)
            req.token_times.append(now)
            self.histories[slot].append(t)
            count += 1
        truncated = granted < need and not req.is_done()
        if req.is_done() or truncated:
            self._finish(slot, now, kv_evicted=truncated)
            return count
        while True:
            status, _ = self.kv.ensure_margin(req.request_id, self._kv_margin)
            if status == "ok":
                break
            if status == "oom":
                victim = self._pick_victim(slot)
                if victim is not None:
                    self._requeue_slot(victim, now)
                    continue
            # context ceiling, or pool dry with nobody left to evict: finish
            # gracefully (truncated) — the same fallback as the dense path
            self._finish(slot, now, kv_evicted=True)
            return count
        self._refresh_bt_row(slot, req.request_id)
        return count

    def _pick_victim(self, protect: int) -> Optional[int]:
        """Eviction victim under page pressure: the lowest-priority active
        slot other than ``protect`` — latest EDF deadline first (best-effort
        requests sort last, so they yield pages to deadline-carrying work),
        ties broken by the highest slot index (deterministic).  None when
        eviction is disabled, unwired, or there is nobody else to evict
        (self-eviction would just thrash: the re-admitted prompt regrows
        into the same dry pool)."""
        if self.econf.kv_evict_policy != "requeue" or self.requeue is None:
            return None
        cands = [s for s in self.active_slots() if s != protect]
        if not cands:
            return None
        return max(cands, key=lambda s: (edf_deadline(self.slot_req[s]), s))

    def _requeue_slot(self, slot: int, now: float) -> None:
        """Evict a decode slot's pages and resubmit its request (it restarts
        from scratch — decode state is positional, not checkpointable)."""
        req = self.slot_req[slot]
        if self.trace.enabled:
            n_freed = len(self.kv.seqs[req.request_id].block_ids)
            self.trace.emit(now, self.worker_id, EV_KV_EVICT, req.request_id,
                            (slot, n_freed))
        self.kv.free_sequence(req.request_id)
        self._clear_slot(slot)
        req.output_tokens.clear()
        req.token_times.clear()
        req.spec_depths.clear()
        req.prefill_active_ticks = 0
        req.kv_requeued += 1
        req.state = RequestState.QUEUED
        if self.trace.enabled:
            self.trace.emit(now, self.worker_id, EV_KV_REQUEUE, req.request_id,
                            (req.kv_requeued,))
        self.requeue(req, now)

    def _clear_slot(self, slot: int) -> None:
        """Release a slot's host bookkeeping (and its block-table row)."""
        self.slot_req[slot] = None
        self.histories[slot] = []
        self._spec_reset_slot(slot)
        if self._paged:
            self._bt_host[slot, :] = -1
            self._bt_dirty = True

    def _finish(self, slot: int, now: float, kv_evicted: bool = False) -> None:
        req = self.slot_req[slot]
        req.state = RequestState.FINISHED
        req.t_end = now
        self.kv.free_sequence(req.request_id)
        rec = _terminal_record(req, now, kv_evicted=kv_evicted)
        self.monitor.complete_request(rec)
        self._clear_slot(slot)
        if self.trace.enabled:
            self.trace.emit(now, self.worker_id, EV_FINISH, req.request_id,
                            (rec.generated, kv_evicted, rec.phase_queued,
                             rec.phase_prefill, rec.phase_decode,
                             rec.phase_stall))

    # ----------------------------------------------------------------- warmup
    def warmup(self, max_prompt_len: Optional[int] = None) -> int:
        """Pre-compile every steady-state shape bucket (prefill batches,
        verify depths, the plain step) ahead of traffic, then reset the lane.
        Returns the number of distinct programs exercised."""
        assert not self.active_slots() and not self.prefill_in_flight(), \
            "warmup() resets the decode and chunk caches; call it before " \
            "serving traffic"
        econf = self.econf
        B = econf.max_batch
        key = jax.random.PRNGKey(0)  # throwaway: must not perturb self.key
        n = 0
        prefill_batches: List[Dict[str, Any]] = []
        if self._chunk is not None:
            # ONE chunk-step program covers every prompt length; also exercise
            # the completion path (chunk-row insert + single-row sample)
            R, C = len(self.chunk_rows), self._chunk
            zeros = jnp.zeros((R,), jnp.int32)
            last, self.chunk_cache = _chunk_step(
                self.lane.model.chunk_prefill, self.chunk_cache,
                self.lane.params,
                jnp.zeros((R, C), jnp.int32), zeros, zeros,
                np.int32(0), np.int32(0),
            )
            if self._paged:
                # sentinel page ids + OOB slot: every write dropped
                self.lane.cache = _tree_insert_pages(
                    self.lane.cache, self.chunk_cache["blocks"], jnp.int32(0),
                    jnp.full((econf.max_len // econf.kv_block_size,),
                             econf.kv_blocks, jnp.int32),
                    jnp.int32(econf.max_batch), jnp.int32(0),
                )
                self.lane.cache = _cache_set_bt(
                    self.lane.cache, jnp.asarray(self._bt_host)
                )
            else:
                self.lane.insert_rows(
                    jnp.full((R,), econf.max_batch, jnp.int32), self.chunk_cache
                )
            sample(key, last, econf.temperature)
            self.chunk_cache = self.lane.model.init_cache(R, econf.max_len)
            n += 1
        elif self._paged:
            # every suffix-length bucket through the paged admit step: the
            # all-(-1) tables drop every page write while the shapes compile
            bt = jnp.asarray(self._bt_host)
            hi = self._bucket(
                min(max_prompt_len or self._max_context, self._max_context),
                self._len_buckets,
            )
            zeros_b = jnp.zeros((B,), jnp.int32)
            for S in (b for b in self._len_buckets if b <= hi):
                last, self.lane.cache = _paged_admit_step(
                    self.lane.model.chunk_prefill, self.lane.params,
                    self.lane.cache, bt, jnp.zeros((B, S), jnp.int32),
                    zeros_b, zeros_b,
                )
                sample(key, last, econf.temperature)
                n += 1
            self.lane.cache = _cache_set_bt(self.lane.cache, bt)
            n += 1
        elif self._bucketed:
            hi = self._bucket(
                min(max_prompt_len or econf.max_len, econf.max_len), self._len_buckets
            )
            drop_all = econf.max_batch  # every warmup insert row is dropped
            for S in (b for b in self._len_buckets if b <= hi):
                for Bb in self._admit_buckets:
                    batch = {
                        "tokens": jnp.zeros((Bb, S), jnp.int32),
                        "lengths": jnp.full((Bb,), S, jnp.int32),
                    }
                    logits, small = self.lane.prefill(batch)
                    self.lane.insert_rows(jnp.full((Bb,), drop_all, jnp.int32), small)
                    # a max_len cache per admit row: kept alive it would sit
                    # beside the next programs' buffers and raise peak HBM
                    del small
                    sample(key, logits, econf.temperature)
                    prefill_batches.append(batch)
                    n += 1
        active_dev = jnp.zeros((B,), bool)
        for d in econf.verify_buckets or ():
            logits = self.lane.decode(jnp.zeros((B, d + 1), jnp.int32))
            verify_tokens(
                key,
                jnp.zeros((B, d), jnp.int32),
                jnp.ones((B, d), jnp.float32),
                logits,
                active=active_dev,
                temperature=econf.temperature,
                depth=jnp.full((B,), d, jnp.int32),
            )
            self.lane.commit(d + 1, jnp.zeros((B,), jnp.int32))
            n += 1
        logits = self.lane.decode(jnp.zeros((B, 1), jnp.int32))  # plain step
        self.lane.commit(1, jnp.zeros((B,), jnp.int32))
        sample(key, logits[:, 0], econf.temperature)
        n += 1
        self.draft.warmup(self, prefill_batches)
        self.lane.reset_cache()
        self.pending = jnp.zeros((B,), jnp.int32)
        return n

    # ---------------------------------------------------------------- metrics
    def publish_metrics(self, queue_depth: int, now: float = 0.0) -> None:
        self.monitor.update_worker(
            self.worker_id,
            cache_hit_rate=self.kv.hit_rate,
            memory_utilization=self.kv.memory_utilization,
            queue_depth=queue_depth,
            active_load=self.load,
            acceptance_rate=self.acceptance,
        )
        if self.trace.enabled:
            depths = [req.spec_depths[-1]
                      for req in self.slot_req
                      if req is not None and req.spec_depths]
            mean_depth = round(sum(depths) / len(depths), 4) if depths else 0.0
            self.trace.emit(
                now, self.worker_id, EV_COUNTERS, None,
                (queue_depth, self.kv.free_blocks, self.kv.pool.used,
                 round(self.acceptance, 6), round(self.load, 6), mean_depth),
            )


class ModelLaneDraft(EngineDraft):
    """Small-transformer draft on its own :class:`ModelLane`, mirroring the
    target's per-slot prefill/insert/commit cache protocol (the EAGLE-class
    production path)."""

    def __init__(self, cfg: ArchConfig, params, max_batch: int, max_len: int,
                 temperature: float):
        self.lane = ModelLane(cfg, params, max_batch, max_len)
        self.temperature = temperature

    def on_admit(self, pair, batch, slots) -> None:
        _, small_cache = self.lane.prefill(batch)
        self.lane.insert_rows(slots, small_cache)

    def propose(self, pair, k: int):
        toks, qs = [], []
        cur = pair.pending[:, None]
        for _ in range(k):
            pair.key, sk = jax.random.split(pair.key)
            logits = self.lane.decode(cur)
            t, q = sample_probs(sk, logits[:, -1], self.temperature)
            toks.append(t)
            qs.append(q)
            cur = t[:, None]
        # the k-th draft token was never ingested by the draft; commit handles
        return jnp.stack(toks, 1), jnp.stack(qs, 1)

    def on_commit(self, pair, accept_idx, k: int) -> None:
        # draft ingested k tokens [pending, d_1..d_{k-1}] during propose; the
        # pre-propose length is recovered inside the jit (donation-safe)
        self.lane.commit(k, jnp.minimum(accept_idx, k - 1))

    def warmup(self, pair, prefill_batches) -> None:
        key = jax.random.PRNGKey(0)
        B = self.lane.max_batch
        for batch in prefill_batches:
            # one OOB (dropped) slot id per prefill ROW — admit buckets may
            # exceed max_batch, so size by the batch, not the lane
            Bb = batch["tokens"].shape[0]
            _, small = self.lane.prefill(batch)
            self.lane.insert_rows(jnp.full((Bb,), B, jnp.int32), small)
        logits = self.lane.decode(jnp.zeros((B, 1), jnp.int32))
        sample_probs(key, logits[:, -1], self.temperature)
        self.lane.commit(1, jnp.zeros((B,), jnp.int32))
        self.lane.reset_cache()


@register_draft("model")
def _make_model_draft(ctx: DraftContext) -> ModelLaneDraft:
    if ctx.draft_cfg is None or ctx.draft_params is None:
        raise ValueError("draft='model' requires draft_cfg and draft_params")
    return ModelLaneDraft(
        ctx.draft_cfg, ctx.draft_params,
        ctx.econf.max_batch, ctx.econf.max_len, ctx.econf.temperature,
    )


class PipeServeEngine:
    """Full StreamServe system on the real JAX execution path (paper Alg 1)."""

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        n_pairs: int = 2,
        econf: Optional[EngineConfig] = None,
        router=None,
        draft_cfg: Optional[ArchConfig] = None,
        draft_params=None,
    ):
        self.econf = econf or EngineConfig()
        if router is None:
            router = resolve_router(self.econf.router, config=self.econf.router_config)
        elif isinstance(router, str):
            router = resolve_router(router, config=self.econf.router_config)
        self._now = 0.0
        # retrace accounting is relative to construction: the lane jit caches
        # are module-level, so earlier engines' traces must not count here
        self._jit_base = self._module_jit_sizes()
        self.monitor = PerformanceMonitor(n_pairs, clock=self._clock)
        self.trace = make_recorder(self.econf.trace, self.econf.trace_capacity)
        self.flight_dumps: List[Dict[str, Any]] = []
        self.pairs = [
            StreamPair(i, cfg, params, self.econf, self.monitor, draft_cfg,
                       draft_params, trace=self.trace)
            for i in range(n_pairs)
        ]
        # SLO routing prices queued prefill work in engine-tick units via the
        # cost model, so TTFT slack is comparable with slo_ttft deadlines.
        # The estimator sees the pairs' EFFECTIVE chunk (None when the arch
        # gate disabled chunking, clamped otherwise) so chunk-per-tick
        # pricing matches what the prefill lane actually serves.
        estimator = None
        if self.econf.slo_routing or self.econf.paged_kv:
            estimator = PrefillDelayEstimator(
                cfg,
                max_batch=self.econf.max_batch,
                mean_context=max(self.econf.max_len // 2, 1),
                prefill_chunk=self.pairs[0]._chunk,
            )
        self.scheduler = StreamScheduler(
            n_pairs, router, self.monitor,
            slo_routing=self.econf.slo_routing,
            delay_estimator=estimator.ticks if estimator else None,
            trace=self.trace,
        )
        self._prefix_estimator = estimator
        if self.econf.paged_kv:
            # prefix-hit-aware routing: probe every pair's radix index per
            # submission; page pressure evicts through the scheduler
            self.scheduler.prefix_probe = self._prefix_score
            for pair in self.pairs:
                pair.requeue = self.scheduler.resubmit_or_fail
        if any(pair._chunk is not None for pair in self.pairs):
            # routing must see requests parked in chunk rows: they left the
            # prefill queue but still owe the lane one tick per chunk left
            self.scheduler.inflight_depth = (
                lambda wid: self.pairs[wid].prefill_in_flight()
            )
            self.scheduler.inflight_delay = self._chunk_backlog_ticks

    def _clock(self) -> float:
        return self._now

    def _prefix_score(self, worker_id: int, req) -> float:
        """Expected prefill saving from a pair's resident prefix pages for a
        new request, as the cost model's saved-work fraction in [0, 1] — the
        routing probe behind FlowGuard's prefix-hit term."""
        hit = self.pairs[worker_id].kv.match_prefix(list(req.prompt))
        if not hit or self._prefix_estimator is None:
            return 0.0
        return self._prefix_estimator.saved_frac(len(req.prompt), hit)

    def _chunk_backlog_ticks(self, worker_id: int) -> float:
        """Remaining chunked-prefill lane turns owed by a pair's chunk rows
        (one chunk per tick), priced into the scheduler's queue delay."""
        pair = self.pairs[worker_id]
        if pair._chunk is None:
            return 0.0
        C = pair._chunk
        return float(sum(
            -(-(len(req.prompt) - pair.chunk_cursor.get(req.request_id, 0)) // C)
            for req in pair.chunk_rows if req is not None
        ))

    # ----------------------------------------------------------------- driving
    def submit(self, req: Request) -> int:
        return self.scheduler.submit(req, self._now)

    def cancel(self, request_id: str) -> bool:
        """Cancel a request wherever it is: still queued (drop from the
        scheduler) or mid-decode (free its slot and KV).  Returns True if the
        request was found and cancelled, False if unknown or already done."""
        req = self.scheduler.cancel(request_id)
        if req is not None:
            req.state = RequestState.CANCELLED
            req.t_end = self._now
            rec = _terminal_record(req, self._now, cancelled=True)
            self.monitor.complete_request(rec)
            self._emit_cancel(req, rec)
            return True
        for pair in self.pairs:
            for slot, req in enumerate(pair.slot_req):
                if req is None or req.request_id != request_id:
                    continue
                pair.kv.free_sequence(req.request_id)
                pair._clear_slot(slot)
                req.state = RequestState.CANCELLED
                req.t_end = self._now
                rec = _terminal_record(req, self._now, cancelled=True)
                self.monitor.complete_request(rec)
                self._emit_cancel(req, rec)
                return True
            # mid-chunked-prefill (parked or active chunk row)
            if pair._chunk is None:
                continue
            for row, req in enumerate(pair.chunk_rows):
                if req is None or req.request_id != request_id:
                    continue
                pair.chunk_release(row)
                req.state = RequestState.CANCELLED
                req.t_end = self._now
                rec = _terminal_record(req, self._now, cancelled=True)
                self.monitor.complete_request(rec)
                self._emit_cancel(req, rec)
                return True
        return False

    def _emit_cancel(self, req: Request, rec: RequestRecord) -> None:
        if self.trace.enabled:
            self.trace.emit(
                self._now, req.worker_id if req.worker_id is not None else -1,
                EV_CANCEL, req.request_id,
                (rec.generated, rec.phase_queued, rec.phase_prefill,
                 rec.phase_decode, rec.phase_stall),
            )

    def fail_worker(self, worker_id: int) -> int:
        """Simulate a node failure: drop the pair, re-route queued AND
        in-flight work (in-flight restarts from scratch — decode state on
        the dead pair is gone)."""
        pair = self.pairs[worker_id]
        pair.healthy = False
        rerouted = self.scheduler.mark_unhealthy(worker_id, self._now)
        orphans: List[Request] = []
        for slot, req in enumerate(pair.slot_req):
            if req is None:
                continue
            pair.kv.free_sequence(req.request_id)
            pair._clear_slot(slot)
            orphans.append(req)
        if pair._chunk is not None:
            for row, req in enumerate(pair.chunk_rows):
                if req is not None:
                    orphans.append(pair.chunk_release(row))
        for req in orphans:
            req.output_tokens.clear()
            req.token_times.clear()
            req.spec_depths.clear()
            req.prefill_active_ticks = 0
            req.state = RequestState.QUEUED
            # FAILED with a terminal record when this was the last worker
            rerouted += self.scheduler.resubmit_or_fail(req, self._now)
        if self.trace.enabled:
            self.trace.emit(self._now, worker_id, EV_WORKER_FAIL, None,
                            (rerouted,))
            self._flight_dump("fail_worker")
        return rerouted

    def step(self) -> int:
        """One engine tick: admit + decode on every healthy pair.  Any
        exception escaping the tick triggers a flight-recorder dump before
        propagating — the post-mortem always holds the last events."""
        try:
            return self._step()
        except Exception:
            if self.trace.enabled:
                self._flight_dump("engine_exception")
            raise

    def _step(self) -> int:
        with TraceAnnotation(SPAN_STEP):
            # logical time: every decision reads ticks; the Request.w_* wall
            # stamps are observation only
            self._now += 1.0
            return sum(self._step_pair(pair) for pair in self.pairs if pair.healthy)

    def _step_pair(self, pair: StreamPair) -> int:
        """One pair's share of a tick: admission, one decode iteration, and
        the metrics it publishes.  Returns the tokens it emitted."""
        wid = pair.worker_id
        pair.counters.steps += 1
        with TraceAnnotation(SPAN_ADMIT):
            if pair._chunk is not None:
                # chunked prefill: one fixed-size chunk per tick, preemptible
                # at the chunk boundary (EDF over in-progress rows + queue)
                pair.chunk_tick(self.scheduler, self._now)
            else:
                self._admit_queued(pair)
        n = pair.decode_iteration(self._now)
        with TraceAnnotation(SPAN_PUBLISH):
            self.monitor.record_tokens(wid, n, self._now)
            pair.publish_metrics(self.scheduler.queue_depth(wid), self._now)
        return n

    def _admit_queued(self, pair: StreamPair) -> None:
        """Stall-free admission: fill free slots from the queue, fusing up to
        ``admit_cap()`` reserved requests into one bucketed prefill call."""
        wid = pair.worker_id
        while True:
            free = pair.free_slots()
            cap = min(len(free), pair.admit_cap())
            batch: List[Request] = []
            blocked = False
            while len(batch) < cap:
                req = self.scheduler.next_for_prefill(wid, self._now)
                if req is None:
                    break
                if not pair.prompt_fits(req):
                    self.scheduler.fail_request(
                        req, self._now, "exceeds_max_context"
                    )
                    continue
                if not pair.reserve_kv(req, self._now):
                    self.scheduler.prefill_queues[wid].appendleft(req)
                    blocked = True
                    break
                batch.append(req)
            if batch:
                pair.admit(batch, self._now)
            if blocked or not batch:
                break

    def counters(self) -> Dict[str, Any]:
        """Cumulative work counters (``repro.obs.counters``) summed over the
        pairs, with each pair's own under ``"pairs"`` (in worker order)."""
        per = [pair.counters.as_dict() for pair in self.pairs]
        total: Dict[str, Any] = {k: sum(c[k] for c in per) for k in per[0]}
        total["pairs"] = per
        return total

    # ------------------------------------------------------------ StreamTrace
    def _flight_dump(self, reason: str) -> Dict[str, Any]:
        """Snapshot the trace ring (flight-recorder dump): kept in memory on
        ``flight_dumps`` and, when ``trace_dir`` is set, written as JSON named
        by reason and engine tick (tick time, not wall time — deterministic)."""
        dump = self.trace.to_dump(reason, self._now)
        self.flight_dumps.append(dump)
        if self.econf.trace_dir:
            import json
            import os

            os.makedirs(self.econf.trace_dir, exist_ok=True)
            path = os.path.join(
                self.econf.trace_dir,
                f"flight_{reason}_tick{int(self._now)}.json",
            )
            with open(path, "w") as f:
                json.dump(dump, f)
        return dump

    def trace_events(self) -> List[Tuple]:
        """All retained trace events, merged across workers in emission order."""
        return self.trace.events()

    def export_chrome_trace(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Chrome-trace/Perfetto JSON of the retained events (written to
        ``path`` when given)."""
        from repro.obs.export import chrome_trace, save_chrome_trace

        if path is not None:
            return save_chrome_trace(self.trace.events(), path)
        return chrome_trace(self.trace.events())

    def prometheus_text(self) -> str:
        """Prometheus text exposition (v0.0.4) of the engine's current state."""
        from repro.obs.export import engine_registry

        return engine_registry(self).render()

    def drained(self) -> bool:
        """True when nothing is queued, mid-chunked-prefill, or decoding."""
        return self.scheduler.pending_total() == 0 and all(
            not p.active_slots() and not p.prefill_in_flight()
            for p in self.pairs if p.healthy
        )

    def run_until_done(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.drained():
                return
            self.step()
        raise RuntimeError("engine did not drain within max_steps")

    def chunk_progress(self) -> Dict[str, int]:
        """Per-request chunked-prefill cursors (tokens ingested so far) across
        all pairs — the observability handle for parked partial prefills."""
        out: Dict[str, int] = {}
        for pair in self.pairs:
            if pair._chunk is not None:
                out.update(pair.chunk_cursor)
        return out

    # ------------------------------------------------------------ warmup/perf
    def warmup(self, max_prompt_len: Optional[int] = None) -> int:
        """Pre-compile every shape bucket on every healthy pair so serving
        triggers zero retraces (``max_prompt_len`` caps the length buckets)."""
        return sum(
            pair.warmup(max_prompt_len) for pair in self.pairs if pair.healthy
        )

    @staticmethod
    def _module_jit_sizes() -> Dict[str, int]:
        """Raw compiled-trace counts of the module-level hot-path jits
        (process-global: every engine's lanes share these caches, keyed by
        each lane's static model closure)."""
        from repro.serving import sampling, speculative

        return {
            "tree_insert": _tree_insert_rows._cache_size(),
            "paged_admit": _paged_admit_step._cache_size(),
            "set_bt": _cache_set_bt._cache_size(),
            "insert_pages": _tree_insert_pages._cache_size(),
            "verify_tokens": speculative.verify_tokens._cache_size(),
            "sample": sampling.sample._cache_size(),
            "sample_probs": sampling.sample_probs._cache_size(),
            "lane_prefill": _lane_prefill._cache_size(),
            "lane_decode": _lane_decode._cache_size(),
            "lane_commit": _lane_commit._cache_size(),
            "chunk_prefill": _chunk_step._cache_size(),
        }

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compiled-trace counts attributable to THIS engine — the retrace
        observability consumed by engine_bench and the regression tests.

        The lane jits are module-level (static model closure keys the cache),
        so counts are reported relative to the snapshot taken at engine
        construction; traces left behind by earlier engines in the same
        process don't bleed in.  The chunked-prefill contract becomes:
        ``chunk_prefill`` == number of chunked lanes (ONE program per lane
        regardless of prompt length).
        """
        base = self._jit_base
        return {
            name: count - base.get(name, 0)
            for name, count in self._module_jit_sizes().items()
        }

    def jit_cache_total(self) -> int:
        return sum(self.jit_cache_sizes().values())
