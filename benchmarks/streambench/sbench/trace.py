"""From a profiler trace to device busy time, kernel time and labelled gaps.

``load_xspace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
only what the reduction needs: the TPU planes' ``XLA Ops`` and ``XLA Modules``
lines, the harness's own host spans (names starting ``sb.``) in the host
plane's lines, and, in a list of their own under ``"program"``, the program's
host spans (names starting ``ss.``).  The result is plain data (lists of
``[name, start_ns, dur_ns, stats]``), so a small recorded trace can be kept as
a test fixture.  ``Reduced`` does the arithmetic on that data:

- the traced window is the host span ``sb.window``;
- busy time is the union of the device op intervals inside it, averaged over
  the chips used;
- a kernel's time is the summed duration of the ops whose own HLO name
  starts with it (the Pallas kernel's ``name=``, as in ``%decode_attention.6``);
- a program's executions are the ``XLA Modules`` events whose name holds the
  jitted function's name;
- each idle gap is labelled by the innermost harness span around its middle;
- a span's idle time is the device idle time inside its interval.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from typing import Any, Dict, List, Tuple

DEVICE_LINES = ("XLA Ops", "XLA Modules")
HARNESS, PROGRAM = "sb.", "ss."   # prefixes of the harness's and the program's spans


def op_name(text: str) -> str:
    """``%copy.112 = bf16[...] copy(...)`` -> ``copy.112``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text: str) -> str:
    """Name and result shape of an op: ``copy.112 bf16[28,16,2048,8,128]``."""
    head, _, rest = text.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}".strip()


def load_xspace(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes, program = [], []
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = []
            for e in line.events:
                if not device and e.name.startswith(PROGRAM):
                    program.append([e.name, float(e.start_ns), float(e.duration_ns), {}])
                if not device and not e.name.startswith(HARNESS):
                    continue
                name = op_label(e.name) if device and line.name == "XLA Ops" else e.name
                events.append([name, float(e.start_ns), float(e.duration_ns), {}])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "program": sorted(program, key=lambda e: e[1])}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Reduced:
    def __init__(self, data: Dict[str, Any]):
        host = [p for p in data["planes"] if p["name"] == "/host:CPU"]
        self.spans: List[Tuple[float, float, str]] = [
            (s, s + d, name) for p in host for ln in p["lines"]
            for name, s, d, _ in ln["events"]]
        win = [(s, e) for s, e, n in self.spans if n == "sb.window"]
        if not win:
            raise ValueError("trace holds no sb.window span")
        self.lo, self.hi = win[0]
        self.devices = [p for p in data["planes"] if p["name"].startswith("/device:TPU:")]
        if not self.devices:
            raise ValueError("trace holds no TPU device plane")
        self.ops: List[List[Tuple[float, float, str, Dict[str, str]]]] = []
        self.modules: List[Tuple[float, float, str]] = []
        for i, p in enumerate(self.devices):
            ops = []
            for ln in p["lines"]:
                for name, s, d, st in ln["events"]:
                    if s + d <= self.lo or s >= self.hi:
                        continue
                    if ln["name"] == "XLA Ops":
                        ops.append((s, s + d, name, st))
                    elif i == 0:
                        self.modules.append((s, s + d, name))
            self.ops.append(ops)
        self.modules.sort()
        self._mod_starts = [m[0] for m in self.modules]
        self.program: List[Tuple[float, float, str]] = [
            (s, s + d, name) for name, s, d, _ in data.get("program", [])]
        self._busy = []   # per device: interval starts, ends, busy ns before each
        for dev in range(len(self.ops)):
            iv = self.busy_intervals(dev)
            before, acc = [], 0.0
            for s, e in iv:
                before.append(acc)
                acc += e - s
            self._busy.append(([s for s, _ in iv], [e for _, e in iv], before))

    # ------------------------------------------------------------ whole device
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_intervals(self, dev: int = 0) -> List[Tuple[float, float]]:
        return _clip(union([(s, e) for s, e, _, _ in self.ops[dev]]), self.lo, self.hi)

    @property
    def busy_s(self) -> float:
        total = sum(e - s for dev in range(len(self.ops)) for s, e in self.busy_intervals(dev))
        return total / len(self.ops) / 1e9

    def _busy_until(self, dev: int, t: float) -> float:
        """Busy ns of device ``dev`` from the window's start to ``t``."""
        starts, ends, before = self._busy[dev]
        i = bisect.bisect_right(starts, t) - 1
        return 0.0 if i < 0 else before[i] + min(t, ends[i]) - starts[i]

    def idle_inside(self, lo: float, hi: float) -> float:
        """Device idle seconds inside the host interval ``[lo, hi]`` (ns, on
        the trace's clock), clipped to the window, averaged over the chips."""
        lo, hi = max(lo, self.lo), min(hi, self.hi)
        if hi <= lo:
            return 0.0
        idle = [(hi - lo) - (self._busy_until(d, hi) - self._busy_until(d, lo))
                for d in range(len(self._busy))]
        return sum(idle) / len(idle) / 1e9

    # ------------------------------------------------------------ host spans
    def spans_named(self, name: str) -> List[Tuple[float, float]]:
        """``(start, end)`` in ns of the host spans (the harness's or the
        program's) named ``name`` that start inside the window, in time order."""
        return sorted((s, e) for s, e, n in self.spans + self.program
                      if n == name and self.lo <= s < self.hi)

    # ------------------------------------------------------------ kernels
    def kernel_s(self, kernel: str, dev: int = 0) -> float:
        """Device seconds of the ops named ``kernel`` or ``kernel.<n>``
        (clipped to the window)."""
        iv = [(s, e) for s, e, n, _ in self.ops[dev]
              if op_name(n).split(" ")[0].rsplit(".", 1)[0] == kernel]
        return sum(e - s for s, e in _clip(iv, self.lo, self.hi)) / 1e9

    def module_runs(self, program: str) -> List[float]:
        """Seconds of each execution of the programs whose name holds ``program``."""
        return [(e - s) / 1e9 for s, e, n in self.modules if program in n]

    # ------------------------------------------------------------ breakdown
    def module_at(self, t: float) -> str:
        i = bisect.bisect_right(self._mod_starts, t) - 1
        if i >= 0 and self.modules[i][1] >= t:
            return self.modules[i][2].split("(", 1)[0]
        return "?"

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        """Device seconds by op (program/name shape), leaf ops only: an op
        that holds others, as a ``while`` holds its body, is left out."""
        ops = sorted(self.ops[0], key=lambda o: (o[0], -o[1]))
        acc: Dict[str, float] = defaultdict(float)
        for i, (s, e, name, _) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < e:
                continue
            acc[f"{self.module_at(s)}/{name}"] += (min(e, self.hi) - max(s, self.lo)) / 1e9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List[Any]]:
        """Idle seconds inside the window, summed by the innermost harness
        span the host was in at the middle of each gap ("none" outside every
        span), most first."""
        busy = self.busy_intervals(0)
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        spans = sorted(sp for sp in self.spans if sp[2] != "sb.window")
        acc: Dict[str, float] = defaultdict(float)
        active: List[Tuple[float, float, str]] = []
        j = 0
        for a, b in zip(edges[0::2], edges[1::2]):   # gaps come in time order
            if b <= a:
                continue
            t = (a + b) / 2
            while j < len(spans) and spans[j][0] <= t:
                active.append(spans[j])
                j += 1
            active = [sp for sp in active if sp[1] >= t]
            label = min(active, key=lambda sp: sp[1] - sp[0])[2] if active else "none"
            acc[label] += (b - a) / 1e9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
