"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is loaded once into plain lists: per device, the ops of its
``XLA Ops`` line as ``(name, start_ns, end_ns, text)``; and the harness's
own host spans (``bench.*`` annotations on the Python thread) as
``(name, start_ns, end_ns)``. Host and device events share the profiler's
clock. Everything below works on those lists, so it is tested on a small
trace recorded on the chip and on hand-made event lists alike.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Op = Tuple[str, float, float, str]      # short name, start, end, full text
Span = Tuple[str, float, float]         # name, start, end

#: the prefix of every host span the harness opens
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    """One traced window: device ops per chip and the harness's spans."""

    devices: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Span] = field(default_factory=list)


def short_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def opcode(text: str) -> str:
    """The HLO opcode of an op's text (``fusion``, ``custom-call``, ...)."""
    if " = " not in text:
        return ""
    rhs = text.split(" = ", 1)[1]
    # skip the result shape: the opcode is the word before the first "("
    # that follows the shape's closing bracket or brace
    depth, i = 0, 0
    while i < len(rhs):
        c = rhs[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    word = rhs[i:].strip().split("(", 1)[0]
    return word.strip()


def load(path_or_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under a directory (or the file)."""
    from jax.profiler import ProfileData

    path = path_or_dir
    if os.path.isdir(path_or_dir):
        found = sorted(glob.glob(os.path.join(path_or_dir, "**",
                                              "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path_or_dir}")
        path = found[-1]
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.devices[plane.name] = [
                        (short_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    tr.spans.sort(key=lambda s: s[1])
    return tr


def union(intervals: Iterable[Tuple[float, float]],
          lo: float = float("-inf"), hi: float = float("inf")
          ) -> List[Tuple[float, float]]:
    """Merged ``[start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some op ran on the device."""
    return sum(e - s for s, e in union(((o[1], o[2]) for o in ops), lo, hi))


def idle_gaps(ops: Sequence[Op], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which no op ran."""
    gaps, t = [], lo
    for s, e in union(((o[1], o[2]) for o in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(spans: Sequence[Span], t: float) -> str:
    """The innermost harness span open at ``t`` (the latest to start), or
    ``"outside"``."""
    best: Optional[Span] = None
    for sp in spans:
        if sp[1] <= t < sp[2] and (best is None or sp[1] >= best[1]):
            best = sp
    return best[0] if best is not None else "outside"


def attribute_gaps(ops: Sequence[Op], spans: Sequence[Span], lo: float,
                   hi: float) -> List[Tuple[str, float]]:
    """Every idle gap as ``(span open at its midpoint, seconds)``, longest
    first."""
    out = [(span_at(spans, (s + e) / 2), (e - s) / 1e9)
           for s, e in idle_gaps(ops, lo, hi)]
    return sorted(out, key=lambda x: -x[1])


def self_times(ops: Sequence[Op]) -> Dict[str, float]:
    """Seconds per op name with the time of ops nested inside it (the body
    of a ``while``, say) taken out, so that no time counts twice."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [name, start, end, nested ns]

    def close(entry):
        name, s, e, nested = entry
        out[name] = out.get(name, 0.0) + (e - s - nested) / 1e9

    for name, s, e, _ in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def matching_ns(ops: Sequence[Op], names: Sequence[str], lo: float,
                hi: float, target: Optional[str] = None) -> Tuple[float, int]:
    """Summed device ns and count of the ops in ``[lo, hi]`` whose short
    name contains one of ``names`` (and whose text names ``target`` as its
    custom-call target, when given)."""
    total, n = 0.0, 0
    for name, s, e, text in ops:
        if s < lo or e > hi or not any(k in name for k in names):
            continue
        if target is not None and f'custom_call_target="{target}"' not in text:
            continue
        total += e - s
        n += 1
    return total, n


def collective_ns(ops: Sequence[Op], lo: float, hi: float,
                  kinds: Sequence[str] = ("collective-permute",)) -> float:
    """Summed device ns of the collective ops of ``kinds`` in the window
    (their ``-start``/``-done`` halves included)."""
    total = 0.0
    for _, s, e, text in ops:
        if s < lo or e > hi:
            continue
        code = opcode(text)
        if any(code == k or code.startswith(k + "-") for k in kinds):
            total += e - s
    return total
