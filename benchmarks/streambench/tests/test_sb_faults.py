"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (the harness, the engine, the check
against the reference) at a CPU size, past the harness's look for a chip,
with one fault planted in the program: a token altered where it is
produced, a decode step that returns its cache unchanged, half of the decode
batch left out.  The cells run on one chip, so there is no exchange between
chips to leave out."""
import functools
import time

import jax

from streambench_testlib import quiet, tiny_cell
from sbench import harness


def _run(cell, seed):
    return harness.run(cell, seed, 2.0, False, time.perf_counter(),
                       require_chip=False, cache=False, log=quiet)


def _assert_caught(r, cell):
    gap = r["checks"]["max_logit_gap"]
    assert r["correct"] is False
    assert gap["value"] > cell.data["max_logit_gap"] == gap["limit"]


def test_token_altered_where_produced(monkeypatch):
    from repro.core import engine

    emit = engine.StreamPair._emit

    def bad_emit(self, slot, tokens, now):
        if slot == 1:
            tokens = [(tokens[0] + 1) % self.lane.cfg.vocab_size, *tokens[1:]]
        return emit(self, slot, tokens, now)

    monkeypatch.setattr(engine.StreamPair, "_emit", bad_emit)
    cell = tiny_cell()
    _assert_caught(_run(cell, 41), cell)


def test_decode_step_returns_cache_unchanged(monkeypatch):
    from repro.core import engine

    @functools.partial(jax.jit, static_argnums=(0,))
    def stale(decode_step, params, cache, tokens):
        logits, _ = decode_step(params, cache, tokens)
        return logits, dict(cache, len=cache["len"] + tokens.shape[1])

    monkeypatch.setattr(engine, "_lane_decode", stale)
    cell = tiny_cell()
    _assert_caught(_run(cell, 42), cell)


def test_half_the_batch_left_out(monkeypatch):
    from repro.core import engine

    @functools.partial(jax.jit, static_argnums=(0,))
    def half(decode_step, params, cache, tokens):
        logits, cache = decode_step(params, cache, tokens)
        B = logits.shape[0]
        return logits.at[B // 2:].set(logits[: B - B // 2]), cache

    monkeypatch.setattr(engine, "_lane_decode", half)
    cell = tiny_cell(rate=40.0)   # enough load that the upper slots fill
    _assert_caught(_run(cell, 43), cell)
