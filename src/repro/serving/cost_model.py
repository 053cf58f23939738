"""Analytic per-operation cost model for the discrete-event simulator.

Wall-clock timing is impossible on this CPU container, so the simulator
prices every engine operation (prefill, decode/verify step, KV transfer,
draft) from first principles: FLOPs / bytes moved against hardware peaks,
with a fixed per-dispatch overhead.  The same model yields the analytic
roofline terms cross-checked against the dry-run's HLO-derived numbers in
EXPERIMENTS.md §Roofline.

Hardware profiles
-----------------
``PEAKS`` holds one row per accelerator, keyed by ``jax.Device.device_kind``
(peak FLOP/s and HBM bandwidth per chip, with their source).  A "lane" is
the model-parallel submesh a prefill or decode worker runs on.
:func:`device_profile` looks up the device the program runs on; a kind that
is not in the table raises instead of borrowing another chip's peaks.

Every op cost is ``max(compute_time, memory_time) + dispatch_overhead``
— the roofline max, not the sum, because TPU/GPU DMA overlaps compute.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    name: str
    peak_flops: float          # per lane, /s
    hbm_bw: float              # bytes/s per lane
    interconnect_bw: float     # bytes/s for KV transfer between lanes
    dispatch_overhead: float   # s per device step (kernel launch, host sync)
    host_staged_bw: float      # bytes/s for the "w/o NIXL" fallback path


# Peak rates per chip, keyed by device_kind.
PEAKS: Dict[str, HardwareProfile] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s, 1,600 Gbit/s of ICI per chip (50 GB/s per link of four).
    "TPU v5 lite": HardwareProfile(
        name="tpu-v5e",
        peak_flops=197e12,
        hbm_bw=819e9,
        interconnect_bw=50e9,      # one ICI link
        dispatch_overhead=25e-6,
        host_staged_bw=8e9,        # PCIe-staged host bounce
    ),
}

TPU_V5E = PEAKS["TPU v5 lite"]


def device_profile(device: Optional[jax.Device] = None) -> HardwareProfile:
    """Peak rates of ``device`` (default: the first JAX device).

    The CPU backend has no row of its own: it prices with the v5e row, an
    explicit stand-in that keeps the engine's tick pricing in CPU tests
    equal to the target chip's.  Any other device kind missing from
    :data:`PEAKS` raises.
    """
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device.device_kind!r}; add its "
            f"row, with a source, to repro.serving.cost_model.PEAKS"
        ) from None


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Prices engine ops for one (arch, hardware, lane-width) deployment."""

    cfg: ArchConfig
    hw: HardwareProfile = TPU_V5E
    lane_chips: int = 1         # chips per prefill/decode worker
    mfu: float = 0.5            # achievable fraction of peak on matmuls
    bw_efficiency: float = 0.55  # achieved fraction of peak HBM bw on
                                 # decode GEMV streams (vLLM-class engines
                                 # measure 0.3-0.6; calibrates absolute TPOT)
    tp_sync_latency: float = 40e-6  # per-allreduce latency within a TP lane
                                 # (2 allreduces / layer); latency-bound at
                                 # decode batch sizes — this is why TP-4
                                 # decode barely beats TP-1 per token (the
                                 # paper's near-equal TPOT row)
    dtype_bytes: int = 2

    # ------------------------------------------------------------ parameters
    @property
    def n_params(self) -> int:
        return self.cfg.n_params()

    @property
    def n_active(self) -> int:
        return self.cfg.n_active_params()

    @property
    def flops_rate(self) -> float:
        return self.hw.peak_flops * self.lane_chips * self.mfu

    @property
    def mem_rate(self) -> float:
        return self.hw.hbm_bw * self.lane_chips * self.bw_efficiency

    def tp_comm_time(self, tokens: int) -> float:
        """Intra-lane tensor-parallel sync: 2 activation all-reduces per
        layer — latency-bound for decode (tiny messages), bandwidth-bound
        for prefill (big messages)."""
        if self.lane_chips <= 1:
            return 0.0
        n_layers = self.cfg.n_layers + self.cfg.n_encoder_layers
        act_bytes = tokens * self.cfg.d_model * self.dtype_bytes
        ring = 2.0 * (self.lane_chips - 1) / self.lane_chips
        per_ar = max(self.tp_sync_latency, act_bytes * ring / self.hw.interconnect_bw)
        return 2.0 * n_layers * per_ar

    def kv_bytes_per_token(self) -> int:
        """KV-cache bytes per token across all attention layers."""
        kinds = self.cfg.layer_kinds()
        n_attn = sum(1 for k in kinds if k == "attn")
        per_layer = 2 * self.cfg.n_kv_heads * self.cfg.head_dim * self.dtype_bytes
        ssm_layers = len(kinds) - n_attn
        # SSM state is O(1) per sequence, amortised to ~0 per token
        return n_attn * per_layer + 0 * ssm_layers

    def ssm_state_bytes(self) -> int:
        if self.cfg.ssm is None:
            return 0
        s = self.cfg.ssm
        nh = s.n_heads(self.cfg.d_model)
        per_layer = nh * s.head_dim * s.d_state * 4  # f32 state
        n_ssm = sum(1 for k in self.cfg.layer_kinds() if k == "ssm")
        return n_ssm * per_layer

    # ------------------------------------------------------------------ ops
    def prefill_time(self, prompt_len: int, cached_tokens: int = 0) -> float:
        """One prompt through the prefill lane (compute-bound).

        ``cached_tokens`` — prefix-cache hits skip recompute (the cache-reuse
        mechanism FlowGuard's C_w signal rewards).
        """
        live = max(prompt_len - cached_tokens, 0)
        flops = 2.0 * self.n_active * live
        # attention quadratic term
        attn_heads = self.cfg.n_heads * self.cfg.head_dim
        n_attn = sum(1 for k in self.cfg.layer_kinds() if k == "attn")
        flops += 4.0 * n_attn * live * max(live, 1) * attn_heads / 2
        t_compute = flops / self.flops_rate
        t_memory = (self.n_active * self.dtype_bytes) / self.mem_rate
        return (
            max(t_compute, t_memory)
            + self.tp_comm_time(live)
            + self.hw.dispatch_overhead
        )

    def chunked_prefill_time(self, prompt_len: int, chunk: int,
                             cached_tokens: int = 0) -> float:
        """Prefill served as ceil(L / chunk) fixed-size chunk steps.

        Each chunk streams the weights again and attends to the full running
        prefix (the quadratic term accumulates across chunks exactly as in
        one-shot prefill), so the overhead of chunking is the per-chunk
        dispatch + weight re-stream — the price of preemptibility that
        DistServe/DynaServe-style schedulers pay for chunk-level elasticity.
        """
        live = max(prompt_len - cached_tokens, 0)
        if live == 0:
            return self.hw.dispatch_overhead
        chunk = max(chunk, 1)
        attn_heads = self.cfg.n_heads * self.cfg.head_dim
        n_attn = sum(1 for k in self.cfg.layer_kinds() if k == "attn")
        weight_stream = (self.n_active * self.dtype_bytes) / self.mem_rate
        total = 0.0
        done = 0
        while done < live:
            n = min(chunk, live - done)
            flops = 2.0 * self.n_active * n
            # chunk queries attend to the prefix ingested so far + themselves
            flops += 4.0 * n_attn * n * max(done + n, 1) * attn_heads / 2
            t_compute = flops / self.flops_rate
            total += (
                max(t_compute, weight_stream)
                + self.tp_comm_time(n)
                + self.hw.dispatch_overhead
            )
            done += n
        return total

    def decode_step_time(self, batch: int, mean_context: float, t_tokens: int = 1) -> float:
        """One decode (or speculative-verify) iteration over a batch.

        Memory-bound: weights are streamed once per step (batch-amortised),
        KV is streamed per sequence.  ``t_tokens`` > 1 (verification) adds
        compute but rides the same weight stream — the marginal cost of
        deeper speculation is small until compute catches memory, which is
        what makes over-speculation (paper Table 9, d=7) unprofitable only
        past the acceptance break-even.
        """
        weight_bytes = self.n_active * self.dtype_bytes
        kv_bytes = batch * mean_context * self.kv_bytes_per_token()
        state_bytes = batch * self.ssm_state_bytes()
        t_memory = (weight_bytes + kv_bytes + state_bytes) / self.mem_rate
        flops = 2.0 * self.n_active * batch * t_tokens
        t_compute = flops / self.flops_rate
        return (
            max(t_compute, t_memory)
            + self.tp_comm_time(batch * t_tokens)
            + self.hw.dispatch_overhead
        )

    def draft_time(self, batch: int, k_tokens: int, draft_frac: float = 0.08,
                   step_overhead: float = 0.6e-3) -> float:
        """k sequential autoregressive steps of a draft ~draft_frac the
        target's size.  The per-step launch latency (EAGLE-class drafts
        measure 1-2 ms/step) is the binding cost of depth — it is why
        over-speculation loses even when verification is memory-bound."""
        weight_bytes = self.n_active * self.dtype_bytes * draft_frac
        per_step = weight_bytes / self.mem_rate + step_overhead
        return k_tokens * per_step

    def kv_transfer_time(self, prompt_len: int, nixl: bool = True) -> float:
        """Prefill -> decode KV handoff (NIXL analogue = ICI-direct resharding;
        the ablation path stages through host memory)."""
        nbytes = prompt_len * self.kv_bytes_per_token() + self.ssm_state_bytes()
        bw = self.hw.interconnect_bw if nixl else self.hw.host_staged_bw
        return nbytes / bw + self.hw.dispatch_overhead


class PrefillDelayEstimator:
    """Prices queued prefill work in *engine-tick* units for SLO routing.

    The engine clock is logical (one tick per step), while the cost model
    prices ops in seconds — the bridge is the decode step itself: one engine
    tick ≈ one batched decode step, so a queued prompt costs its cost-model
    prefill + KV-transfer time divided by the decode-step time.  Long prompts
    (sum: ~600 tokens) therefore delay a queue by many tick-equivalents while
    short chat prompts cost ~1, which is exactly the asymmetry FlowGuard's
    TTFT-slack term and the EDF admission guard need to see.
    """

    def __init__(self, cfg: ArchConfig, max_batch: int = 8,
                 mean_context: int = 256, prefill_chunk: Optional[int] = None):
        self.cost = CostModel(cfg, hw=device_profile())
        self.tick_s = self.cost.decode_step_time(max_batch, max(mean_context, 1))
        self.prefill_chunk = prefill_chunk

    def ticks(self, req) -> float:
        """Estimated service ticks to prefill one queued request.

        With chunked prefill (``prefill_chunk``) the engine's prefill lane
        serves exactly ONE chunk per tick, so service time is quantised at
        ceil(prompt / chunk) ticks — the long/short asymmetry the EDF
        preemption exploits, and the quantity FlowGuard's queue-delay
        estimate must reflect for its TTFT-slack scores to stay honest.

        Memoised on the request (its prompt never changes while queued), so
        re-scoring a deep queue on every submission stays O(queue) additions
        instead of O(queue) cost-model evaluations.
        """
        cached = getattr(req, "_prefill_ticks", None)
        if cached is not None:
            return cached
        plen = len(req.prompt)
        if self.prefill_chunk:
            # chunk-per-tick service quantisation dominates any sub-tick cost
            t = float(max(-(-plen // self.prefill_chunk), 1))
        else:
            t = self.cost.prefill_time(plen, getattr(req, "cache_hit_tokens", 0))
            t += self.cost.kv_transfer_time(plen)
            t = max(t / self.tick_s, 1.0)
        req._prefill_ticks = t
        return t

    def saved_ticks(self, prompt_len: int, hit_tokens: int) -> float:
        """Prefill ticks a resident radix prefix of ``hit_tokens`` saves for
        a ``prompt_len`` prompt — the absolute prefill work a prefix-hit
        route avoids, in the same tick units as :meth:`ticks`."""
        hit = min(max(hit_tokens, 0), prompt_len)
        if hit == 0:
            return 0.0
        if self.prefill_chunk:
            full = max(-(-prompt_len // self.prefill_chunk), 1)
            rem = max(-(-(prompt_len - hit) // self.prefill_chunk), 1)
            return float(full - rem)
        full = self.cost.prefill_time(prompt_len)
        rem = self.cost.prefill_time(prompt_len, cached_tokens=hit)
        return max(full - rem, 0.0) / self.tick_s

    def saved_frac(self, prompt_len: int, hit_tokens: int) -> float:
        """Saved prefill work as a fraction of the full prompt's prefill
        cost, clamped to [0, 1] — the normalised prefix-hit score FlowGuard's
        ``prefix_weight`` term consumes.

        When prefill is memory-bound the roofline wall-time delta degenerates
        to ~0 (the weight stream floors both sides), but the hit still skips
        the prefix's flops and KV writes — fall back to the token fraction so
        the routing signal survives the memory-bound regime.
        """
        hit = min(max(hit_tokens, 0), prompt_len)
        if prompt_len <= 0 or hit == 0:
            return 0.0
        if self.prefill_chunk:
            full = float(max(-(-prompt_len // self.prefill_chunk), 1))
        else:
            full = self.cost.prefill_time(prompt_len) / self.tick_s
        frac = self.saved_ticks(prompt_len, hit) / full if full > 0.0 else 0.0
        if frac <= 0.0:
            frac = hit / prompt_len
        return min(max(frac, 0.0), 1.0)
