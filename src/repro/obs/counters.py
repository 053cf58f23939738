"""Cumulative work counters of one stream pair.

Plain integers the engine adds to as it works: a few adds per pair per step,
always on, no device values.  ``PipeServeEngine.counters()`` reads them
(summed over the pairs and per pair) and ``engine_registry`` exports them as
``streamserve_<name>_total``.  The work of a window is the difference of two
readings.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

COUNTER_HELP: Dict[str, str] = {
    "steps": "Engine steps the pair served",
    "decode_calls": "Plain one-token decode programs run",
    "verify_calls": "Speculative verify programs run",
    "spec_proposed": "Draft tokens proposed: sum of each active row's depth over verify steps",
    "spec_accepted": "Draft tokens accepted by those rows",
    "prefill_calls": "Prefill programs run (admit batches, paged admits, chunks)",
    "prefill_live_tokens": "Prompt tokens the prefill programs fed",
    "prefill_slot_tokens": "Positions the prefill programs computed (bucket batch x length)",
}


@dataclasses.dataclass(slots=True)
class WorkCounters:
    steps: int = 0
    decode_calls: int = 0
    verify_calls: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0
    prefill_calls: int = 0
    prefill_live_tokens: int = 0
    prefill_slot_tokens: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)
