"""Profiler trace -> device intervals and host spans, and the shared
reductions the per-layer readers build on.

A run with ``--trace 1`` records one JAX profiler trace of a steady
window.  :func:`load` reads its ``.xplane.pb`` with nothing but JAX and
keeps four things: the device operations (``XLA Ops`` lines of each TPU
plane), the device programs (``XLA Modules`` lines), the benchmark's own
host spans (``bench.*`` TraceAnnotations) and the traced window.  A
:class:`Trace` is plain data, so a small recorded one can be committed
and the reductions checked against it without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the device programs of the VM engines (the mixed and batched engines'
# and the compiled trace's jitted ``run``, the sharded engine's jitted
# shard_map), of the serving engine's jitted prefill and decode
# lambdas, and the paged-attention kernel's operations
FABRIC_WAVE_PROGRAM = r"^jit_(run|device_body|sharded)\b"
MODEL_PROGRAM = r"^jit__lambda"
PAGED_ATTENTION_OP = r"^%paged_attention"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Trace:
    """Times in nanoseconds on the profiler's one clock.

    ``ops`` and ``modules`` hold ``(chip, name, start, end)``; ``spans``
    holds ``(name, start, end)``; ``window`` is the traced window."""

    window: Interval
    chips: List[int]
    ops: List[Tuple[int, str, int, int]]
    modules: List[Tuple[int, str, int, int]]
    spans: List[Tuple[str, int, int]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(window=tuple(d["window"]), chips=list(d["chips"]),
                   ops=[tuple(x) for x in d["ops"]],
                   modules=[tuple(x) for x in d["modules"]],
                   spans=[tuple(x) for x in d["spans"]])

    def excerpt(self, start: int, end: int) -> "Trace":
        """The part of the trace inside ``[start, end)``, every interval
        clipped to it and every operation under its short name: a small
        sample to commit beside the tests."""
        def clip(s: int, e: int):
            return (max(s, start), min(e, end)) if e > start and s < end \
                else None

        ops = [(c, op_name(n)) + clip(s, e) for c, n, s, e in self.ops
               if clip(s, e)]
        modules = [(c, n) + clip(s, e) for c, n, s, e in self.modules
                   if clip(s, e)]
        spans = [(n,) + clip(s, e) for n, s, e in self.spans
                 if clip(s, e) and n != WINDOW_SPAN]
        return Trace(window=(start, end), chips=self.chips, ops=ops,
                     modules=modules, spans=spans)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Trace:
    """Read the newest profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops: List[Tuple[int, str, int, int]] = []
    modules: List[Tuple[int, str, int, int]] = []
    spans: List[Tuple[str, int, int]] = []
    chips: List[int] = []
    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            chips.append(chip)
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    dest.append((chip, ev.name, s,
                                 s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    return Trace(window=windows[-1], chips=sorted(chips), ops=ops,
                 modules=modules, spans=spans)


# ---------------------------------------------------------------------------
# interval arithmetic


def union(intervals: Iterable[Interval], lo: int, hi: int
          ) -> List[Interval]:
    """Merged intervals, clipped to ``[lo, hi)``."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` inside ``[lo, hi)``."""
    out: List[Interval] = []
    cur = lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def busy(trace: Trace, chip: int) -> List[Interval]:
    """Merged intervals in which an operation ran on ``chip``."""
    lo, hi = trace.window
    return union(((s, e) for c, _, s, e in trace.ops if c == chip), lo, hi)


def busy_s(trace: Trace) -> float:
    """Seconds with an operation running, averaged over the chips."""
    if not trace.chips:
        return 0.0
    return sum(total(busy(trace, c)) for c in trace.chips) \
        / len(trace.chips) * 1e-9


def idle_share(trace: Trace, chip: int) -> float:
    lo, hi = trace.window
    return 1.0 - total(busy(trace, chip)) / (hi - lo)


def span_at(trace: Trace, t: int) -> str:
    """The innermost host span that covers time ``t``."""
    best: Optional[Tuple[str, int, int]] = None
    for name, s, e in trace.spans:
        if name == WINDOW_SPAN or not s <= t < e:
            continue
        if best is None or e - s < best[2] - best[1]:
            best = (name, s, e)
    return best[0] if best else WINDOW_SPAN


def module_runs(trace: Trace, chip: int, pattern: str
                ) -> List[Interval]:
    """Intervals of the device programs whose name matches ``pattern``
    (a regular expression), in the window, on ``chip``."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return sorted((max(s, lo), min(e, hi))
                  for c, n, s, e in trace.modules
                  if c == chip and rx.search(n) and e > lo and s < hi)


def most_runs(trace: Trace, chip: int, pattern: str) -> List[Interval]:
    """The runs of the program, among those whose name matches
    ``pattern``, that ran most often in the window: the decode step among
    the serving engine's programs, which also compiles one prefill per
    prompt bucket under the same name."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    by_name: Dict[str, List[Interval]] = {}
    for c, n, s, e in trace.modules:
        if c == chip and rx.search(n) and e > lo and s < hi:
            by_name.setdefault(n, []).append((max(s, lo), min(e, hi)))
    if not by_name:
        return []
    return sorted(max(by_name.values(), key=len))


def op_time(trace: Trace, chip: int, pattern: str) -> int:
    """Nanoseconds of device operations whose short name (see
    :func:`op_name`) matches ``pattern``."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return total(union(((s, e) for c, n, s, e in trace.ops
                        if c == chip and rx.search(op_name(n))), lo, hi))


def op_name(hlo: str) -> str:
    """An operation's short name: ``%while.65`` of the HLO text the
    trace gives, with the kind of operation beside it."""
    head, _, rest = hlo.partition(" = ")
    m = re.search(r"\b([a-z][a-z0-9-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took the most time (seconds, summed
    over chips), and the longest idle gaps of the first chip, each named
    by the host span it fell in."""
    lo, hi = trace.window
    per_op: Dict[str, int] = {}
    for _, name, s, e in trace.ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            name = op_name(name)
            per_op[name] = per_op.get(name, 0) + d
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle: List[Tuple[str, float]] = []
    if trace.chips:
        g = gaps(busy(trace, trace.chips[0]), lo, hi)
        g.sort(key=lambda x: x[0] - x[1])
        idle = [(span_at(trace, (s + e) // 2), (e - s) * 1e-9)
                for s, e in g[:top]]
    return {"device_ops": [[n, d * 1e-9] for n, d in ops],
            "idle_gaps": [[n, d] for n, d in idle]}
