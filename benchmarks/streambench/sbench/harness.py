"""One run of one cell: set up, warm in, measure, check, report.

    set-up    weights from the seed (one jitted call), the engine, warm-up of
              the shapes this cell's traffic uses, and a warm-in of traffic
    window    ``seconds`` of the cell's traffic, timed by the host clock; with
              ``trace`` the first ``trace_s`` of it run under the profiler
    check     the program's state is freed, the reference regenerates the
              weights and scores a sample of the finished requests

Clock.  The engine counts logical ticks; a tick becomes a time here: a
token is seen at the end of the ``step()`` that produced it, and a prefill
starts at the start of the ``step()`` that admitted it, both on this
module's ``perf_counter``.  Each request is timed from when it was due.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from sbench import spec as specmod
from sbench.traffic import make_plan, seed_words

clock = time.perf_counter
NULL = contextlib.nullcontext()


# ------------------------------------------------------------------ statistics
def percentile(vals: List[float], p: float) -> Optional[float]:
    """Nearest rank: ceil(p/100 * n) - 1 of the sorted values."""
    if not vals:
        return None
    vals = sorted(vals)
    return vals[max(math.ceil(p / 100.0 * len(vals)) - 1, 0)]


class NoChip(RuntimeError):
    pass


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform!r} ({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def compile_cache_dir() -> str:
    """The checkout's own cache directory; an inherited one is taken only
    when it lies inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    root = specmod.ROOT.resolve()
    if env and Path(env).resolve().is_relative_to(root):
        return env
    return str(root / ".jax_cache")


def enable_cache() -> str:
    import jax

    path = compile_cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ requests
class Req:
    __slots__ = ("i", "due", "submit", "handle")

    def __init__(self, i: int, due: float):
        self.i, self.due = i, due
        self.submit: Optional[float] = None
        self.handle = None

    @property
    def r(self):
        return self.handle.request


class Recorder:
    """Per-call records of the program's device work in the traced window:
    live rows of every decode/verify call and live prompt lengths of every
    prefill call, read from the pair's host state at the call.  Installed
    only in traced runs, around the pair's own entry points."""

    def __init__(self, serve, ann: Callable[[str], Any]):
        self.on = False
        self.decode: List[Tuple[int, int, List[Tuple[int, int]]]] = []  # (B, T, rows)
        self.prefill: List[List[int]] = []
        for pair in serve.engine.pairs:
            self._wrap(pair, ann)

    def _wrap(self, pair, ann) -> None:
        decode, admit, propose = pair.lane.decode, pair.admit, pair.draft.propose

        def lane_decode(tokens):
            if self.on:
                T = int(tokens.shape[1])
                rows = []
                for req in pair.slot_req:
                    if req is None:
                        continue
                    cached = req.prompt_len + len(req.output_tokens) - 1
                    rows.append((cached, 1 if T == 1 else int(req.spec_depths[-1]) + 1))
                self.decode.append((len(pair.slot_req), T, rows))
            with ann("sb.decode"):
                return decode(tokens)

        def pair_admit(reqs, now):
            if self.on:
                self.prefill.append([r.prompt_len for r in reqs])
            with ann("sb.admit"):
                return admit(reqs, now)

        def draft_propose(p, k):
            with ann("sb.draft"):
                return propose(p, k)

        pair.lane.decode, pair.admit, pair.draft.propose = lane_decode, pair_admit, draft_propose


def live_cached_tokens(eng) -> int:
    """Tokens the decode slots' KV cache holds for live requests: each live
    row's prompt and its answer so far (the host's view after a step)."""
    return sum(req.prompt_len + len(req.output_tokens)
               for pair in eng.pairs for req in pair.slot_req if req is not None)


def memory() -> Dict[str, int]:
    """Bytes in use and the process's peak so far on the first chip."""
    import jax

    st = jax.devices()[0].memory_stats() or {}
    return {"in_use": int(st.get("bytes_in_use", 0)),
            "peak": int(st.get("peak_bytes_in_use", 0))}


class Driver:
    """Feeds the plan to ``StreamServe`` and steps it, on the host clock."""

    def __init__(self, serve, plan, origin: float, ann: Callable[[str], Any]):
        self.serve, self.plan, self.origin, self.ann = serve, plan, origin, ann
        self.eng = serve.engine
        self.reqs: List[Req] = []
        self.live: List[Req] = []
        self.next_i = 0
        self.tick_start: Dict[float, float] = {}
        self.tick_end: Dict[float, float] = {}
        self.spec = None   # [verify row-steps, tokens they emitted, depth sum] when counting
        self._seen: Dict[int, Tuple[int, int]] = {}
        self.kv_tokens: Optional[List[int]] = None   # live cached tokens after each step

    def _submit(self, req: Req) -> None:
        from repro.serving.request import SamplingParams

        i = req.i % len(self.plan)
        req.submit = clock()
        req.handle = self.serve.submit(
            self.plan.prompts[i].tolist(),
            SamplingParams(temperature=0.0, max_new_tokens=int(self.plan.answers[i])))
        self.reqs.append(req)
        self.live.append(req)

    def _submit_due(self, now: float) -> None:
        due = self.plan.due
        while self.next_i < len(due) and self.origin + due[self.next_i] <= now:
            self._submit(Req(self.next_i, self.origin + due[self.next_i]))
            self.next_i += 1

    def _harvest(self, tick: float) -> None:
        still = []
        for req in self.live:
            r = req.r
            if self.spec is not None:
                n_out, n_sd = len(r.output_tokens), len(r.spec_depths)
                o0, s0 = self._seen.get(req.i, (0, 0))
                if n_sd > s0:
                    emitted = n_out - o0 - (1 if r.t_first_token == tick else 0)
                    self.spec[0] += 1
                    self.spec[1] += emitted
                    self.spec[2] += r.spec_depths[-1]
                self._seen[req.i] = (n_out, n_sd)
            if not req.handle.done:
                still.append(req)
        self.live = still
        if self.kv_tokens is not None:
            self.kv_tokens.append(live_cached_tokens(self.eng))

    def drive(self, until: float) -> float:
        """Serve the plan until the host clock reaches ``until``; returns the
        time the last step ended (or ``until``)."""
        ann = self.ann
        while True:
            now = clock()
            with ann("sb.submit"):   # before the close too: nothing due goes unsent
                self._submit_due(now)
            if now >= until:
                return now
            if self.live:
                t0 = clock()
                with ann("sb.step"):
                    self.serve.step()
                t1 = clock()
                tick = self.eng._now
                self.tick_start[tick], self.tick_end[tick] = t0, t1
                with ann("sb.harvest"):
                    self._harvest(tick)
            else:
                nxt = (self.origin + self.plan.due[self.next_i]
                       if self.next_i < len(self.plan.due) else until)
                with ann("sb.sleep"):
                    time.sleep(max(0.0, min(nxt, until) - clock()))

    def sync(self) -> None:
        import jax

        jax.block_until_ready([(p.lane.cache, p.pending) for p in self.eng.pairs])

    # --------------------------------------------------------------- readings
    def when(self, tick: Optional[float], start: bool = False) -> Optional[float]:
        if tick is None:
            return None
        return (self.tick_start if start else self.tick_end).get(tick)


# ------------------------------------------------------------------ metrics
def window_stats(drv: Driver, lo: float, hi: float) -> Dict[str, Any]:
    """Host-clock readings of the window [lo, hi)."""
    due = [q for q in drv.reqs if lo <= q.due < hi]
    ttft, lag, qwait = [], [], []
    for q in due:
        r = q.r
        first = drv.when(r.t_first_token)
        ttft.append((first if first is not None and first <= hi else hi) - q.due)
        lag.append(q.submit - q.due)
        start = drv.when(r.t_prefill_start, start=True)
        qwait.append((start if start is not None and start <= hi else hi) - q.due)
    tpot, tokens = [], 0
    for q in drv.reqs:
        r = q.r
        times = [drv.when(t) for t in r.token_times]
        tokens += sum(1 for t in times if t is not None and lo < t <= hi)
        if r.state.value == "finished" and len(times) >= 2 and lo < times[-1] <= hi:
            tpot.append((times[-1] - times[0]) / (len(times) - 1))
    failed = sum(1 for q in due if q.r.state.value in ("failed", "cancelled"))
    return {"ttft": ttft, "tpot": tpot, "lag": lag, "qwait": qwait,
            "tokens": tokens, "seconds": hi - lo, "attempted": len(due), "failed": failed}


def end_to_end(name: str, st: Dict[str, Any], setup_s: float) -> float:
    """The end-to-end metrics by name: ``setup_s``, ``ttft_p<q>_s`` and
    ``tpot_p<q>_s`` (nearest-rank percentile q)."""
    if name == "setup_s":
        return setup_s
    for prefix in ("ttft", "tpot"):
        if name.startswith(prefix + "_p") and name.endswith("_s"):
            v = percentile(st[prefix], float(name[len(prefix) + 2:-2]))
            if v is None:
                raise RuntimeError(f"{name}: no {prefix} sample in the window")
            return v
    raise KeyError(f"no end-to-end metric {name!r}")


# ------------------------------------------------------------------ check
def pick_sample(drv: Driver, seed: int, ref_tokens: int, max_requests: int) -> List[Req]:
    """The longest finished request, then others in a seeded order, taking
    the two pairs in turn, until ``ref_tokens`` served tokens are in."""
    done = [q for q in drv.reqs if q.r.state.value == "finished"]
    if not done:
        return []
    longest = max(done, key=lambda q: (len(q.r.output_tokens), -q.i))
    rest = [q for q in done if q is not longest]
    order = seed_words(seed, 4).permutation(len(rest))
    by_pair: Dict[int, List[Req]] = {}
    for j in order:
        by_pair.setdefault(rest[j].r.worker_id, []).append(rest[j])
    queues = [by_pair[k] for k in sorted(by_pair)]
    out, n = [longest], len(longest.r.output_tokens)
    while n < ref_tokens and len(out) < max_requests and any(queues):
        for qu in queues:
            if qu and n < ref_tokens and len(out) < max_requests:
                q = qu.pop(0)
                out.append(q)
                n += len(q.r.output_tokens)
    return out


def reference_gaps(cell: specmod.Cell, seed: int,
                   sample: List[Tuple[List[int], List[int]]],
                   control: Optional[str] = None):
    """Per request, at each served position, the family's reference's best
    logit minus its logit of the served token.  With ``control``, also the
    same gap for the token that the lower-precision control puts first there.
    Returns ``(served_gaps, control_gaps or None)``, one array per request."""
    import jax
    import jax.numpy as jnp

    cfg, mix, ref_mod = cell.config, cell.traffic, cell.reference
    w = cell.family.make_weights(cfg, seed)
    n_pos = int(mix["answer"]["max"])
    L = -(-(int(mix["prompt"]["max"]) + n_pos) // 128) * 128
    served_gaps, control_gaps = [], []
    for prompt, served in sample:
        toks = np.zeros((L,), np.int32)
        seq = list(prompt) + list(served)
        toks[: len(seq)] = seq
        start = len(prompt) - 1
        ref = ref_mod.logits_at(cfg, w, toks, start, n_pos)
        best = ref.max(-1)

        def gap(pick):
            return np.asarray(jax.device_get(
                best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]))[: len(served)]

        served_gaps.append(gap(jnp.asarray(np.pad(served, (0, n_pos - len(served))), jnp.int32)))
        if control is not None:
            low = ref_mod.logits_at(cfg, w, toks, start, n_pos, quant=control)
            control_gaps.append(gap(jnp.argmax(low, -1).astype(jnp.int32)))
    del w
    return served_gaps, (control_gaps if control is not None else None)


def widest(gaps: List[np.ndarray]) -> float:
    return float(max((g.max() for g in gaps if len(g)), default=float("inf")))


# ------------------------------------------------------------------ the run
def run(cell: specmod.Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_chip: bool = True, cache: bool = True, control: Optional[str] = None,
        log=None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object (last stdout line).
    ``control`` also reads the lower-precision control on the same sample
    (``result["control"]``); the benchmark's own runs leave it off."""
    import jax

    from sbench import program

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    phases = [("python", clock() - t_start)]   # set-up, phase by phase

    def phase(name: str, t0: float) -> float:
        t1 = clock()
        phases.append((name, t1 - t0))
        return t1

    t = clock()
    if require_chip:
        dev = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        dev = {"platform": d.platform, "kind": d.device_kind, "count": cell.chips}
    cache = enable_cache() if cache else "off"
    mix, data, cfg = cell.traffic, cell.data, cell.config
    rate = float(data.get("rate_per_s", 0.0))
    plan = make_plan(mix, seed, seconds, int(cfg["vocab_size"]), rate)
    log(f"streambench {cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={dev} cache={cache} requests_planned={len(plan)} rate_per_s={rate}")
    t = phase("jax_init", t)

    family = cell.family
    w = jax.block_until_ready(family.make_weights(cfg, seed))
    t = phase("weights", t)
    serve = program.build(family, cfg, w)
    del w
    t = phase("engine", t)
    programs = serve.engine.warmup(max_prompt_len=int(mix["prompt"]["max"]))
    jax.block_until_ready([p.lane.cache for p in serve.engine.pairs])
    t = phase("warmup", t)
    mem_warm = memory()

    if trace:
        ann = jax.profiler.TraceAnnotation
        rec = Recorder(serve, ann)
    else:
        ann, rec = (lambda name: NULL), None
    origin = clock()
    drv = Driver(serve, plan, origin, ann)
    drv.drive(origin + float(mix["warm_in_s"]))
    drv.sync()
    t = phase("warm_in", t)
    trace_dir = None
    trace_s = min(float(mix.get("trace_s", seconds)), seconds)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="streambench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = serve.engine.jit_cache_total()
    mem_open = memory()
    drv.kv_tokens = [live_cached_tokens(serve.engine)]
    w0 = phase("profiler" if trace else "window_open", t)
    setup_s = w0 - t_start
    log("streambench setup: " + " ".join(f"{k}={v:.3f}s" for k, v in phases)
        + f" programs_warmed={programs} total={setup_s:.3f}s")
    if trace:
        counts0 = program_counters(serve)
        rec.on = True
        drv.spec = [0, 0, 0]
        drv._seen = {q.i: (len(q.r.output_tokens), len(q.r.spec_depths)) for q in drv.live}
        with jax.profiler.TraceAnnotation("sb.window"):
            t_traced = drv.drive(w0 + trace_s)
            drv.sync()
        rec.on = False
        spec_counts, drv.spec = drv.spec, None
        counts = counter_diff(counts0, program_counters(serve))
        jax.profiler.stop_trace()
    t_close = drv.drive(w0 + seconds)
    drv.sync()
    compiles = serve.engine.jit_cache_total() - compiles0
    stats = window_stats(drv, w0, t_close)
    mem_close = memory()
    dev["memory_peak_bytes"] = mem_close["peak"]
    kv_tok = drv.kv_tokens
    kv_per_tok = family.kv_bytes_per_token(cfg)
    log(f"streambench memory: after warm-up in_use={mem_warm['in_use']} "
        f"peak={mem_warm['peak']}; window open in_use={mem_open['in_use']} "
        f"peak={mem_open['peak']}; close in_use={mem_close['in_use']} "
        f"peak={mem_close['peak']}; live KV in the window mean="
        f"{kv_per_tok * sum(kv_tok) / len(kv_tok):.0f} max={kv_per_tok * max(kv_tok)} B "
        f"({kv_per_tok} B/token, {len(kv_tok)} samples)")
    log(f"streambench window: {t_close - w0:.3f}s steps={len(drv.tick_end)} "
        f"submitted={len(drv.reqs)} due_in_window={stats['attempted']} "
        f"tokens_in_window={stats['tokens']} programs_warmed={programs} "
        f"compiles_in_window={compiles} setup_s={setup_s:.3f}")

    result: Dict[str, Any] = {"correct": False, "attempted": stats["attempted"],
                              "failed": stats["failed"], "metrics": {}, "device": dev}
    if trace:
        from sbench import trace as tr

        red = tr.Reduced(tr.load_xspace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
        # what a per-layer metric reader (metrics/<name>.py) sees
        ctx = SimpleNamespace(cell=cell, cfg=cfg, family=family, trace=red,
                              decode_calls=rec.decode, prefill_calls=rec.prefill,
                              spec=spec_counts, host=window_stats(drv, w0, t_traced),
                              peaks=load_peaks(dev["kind"]), counters=counts,
                              requests=[q.r for q in drv.reqs if w0 <= q.due < t_traced],
                              close=t_traced)
        for m in cell.per_layer:
            v = specmod.metric_reader(m["name"], cell.metrics_dir)(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": end_to_end(m["name"], stats, setup_s), "unit": m["unit"]}

    # ---- check: free the program, then the reference over a sample
    sample = pick_sample(drv, seed, int(mix["ref_tokens"]),
                         int(mix["ref_max_requests"]))
    pairs = [(list(q.r.prompt), list(q.r.output_tokens)) for q in sample]
    mismatched = sum(1 for q in drv.reqs if q.r.state.value == "finished"
                     and len(q.r.output_tokens) != q.r.params.max_new_tokens)
    finished = sum(1 for q in drv.reqs if q.r.state.value == "finished")
    workers = sorted({q.r.worker_id for q in sample})
    del serve, drv, sample, rec
    gc.collect()
    t_ref = clock()
    gaps, cgaps = reference_gaps(cell, seed, pairs, control)
    gap = widest(gaps)
    n_cmp = int(sum(len(g) for g in gaps))
    limit = data.get("max_logit_gap")
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit, "must": "<="},
        "answer_length_mismatches": {"value": mismatched, "limit": 0, "must": "<="},
        "served_tokens_compared": {"value": n_cmp, "limit": int(mix["ref_min_tokens"]),
                                   "must": ">="},
    }
    result["correct"] = bool(
        limit is not None and gap <= limit and mismatched == 0
        and n_cmp >= int(mix["ref_min_tokens"]))
    log(f"streambench reference: {len(pairs)} requests on pairs {workers}, "
        f"{n_cmp} served tokens of {finished} finished requests, "
        f"{clock() - t_ref:.3f}s")
    if cgaps is not None:
        result["control"] = {"max_logit_gap": widest(cgaps)}
        log(f"streambench control {control}: max_logit_gap {widest(cgaps)}")
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['must']} {c['limit']})")
    return result


def program_counters(serve) -> Optional[Dict[str, Any]]:
    """The program's own work counters (``engine.counters()``), or None where
    the program keeps none."""
    counters = getattr(serve.engine, "counters", None)
    return None if counters is None else counters()


def counter_diff(before: Optional[Dict[str, Any]],
                 after: Optional[Dict[str, Any]]) -> Optional[Dict[str, int]]:
    """The program's work counters over an interval: each total's growth
    (summed over the pairs; the per-pair ``"pairs"`` left out)."""
    if before is None or after is None:
        return None
    return {k: after[k] - before[k] for k in after if k != "pairs"}


def load_peaks(kind: str) -> Dict[str, Any]:
    peaks = specmod.load_json(specmod.BENCH_DIR / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def emit(result: Dict[str, Any]) -> None:
    print(json.dumps(result), flush=True)
