"""Arithmetic of the window: quantiles, rates, span self times and counter deltas."""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (``statistics.quantiles(..., method='inclusive')``)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no values")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(work: Iterable[float], start: float, end: float) -> float:
    """Work completed per second over the whole window [start, end]."""
    span = end - start
    if span <= 0:
        raise ValueError(f"empty window [{start}, {end}]")
    return sum(work) / span


def _cover(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def self_times(spans: Sequence[Dict]) -> List[float]:
    """Each span's duration less the part that its child spans (same trace,
    ``tags.parent`` naming it, inside it) cover, in seconds."""
    kids = defaultdict(list)
    for s in spans:
        parent = s["tags"].get("parent")
        if parent is not None:
            kids[(s["trace_id"], parent)].append(s)
    out = []
    for s in spans:
        inside = [(max(c["t_start"], s["t_start"]), min(c["t_end"], s["t_end"]))
                  for c in kids.get((s["trace_id"], s["name"]), ())
                  if c is not s]
        inside = [(a, b) for a, b in inside if b > a]
        out.append(s["t_end"] - s["t_start"] - _cover(inside))
    return out


def per_trace_sum(spans: Sequence[Dict], name: str) -> List[float]:
    """Summed self time of the spans called ``name``, per trace id (one
    trace per codeword for the pool's spans), in seconds."""
    sums: Dict[str, float] = defaultdict(float)
    for s, st in zip(spans, self_times(spans)):
        if s["name"] == name:
            sums[s["trace_id"]] += st
    return list(sums.values())


def mean(xs: Sequence[float]):
    """The mean, or None for no values (a reader then reports nothing)."""
    return sum(xs) / len(xs) if xs else None


def delta(after: Dict, before: Dict, key: str) -> float:
    return float(after.get(key, 0) or 0) - float(before.get(key, 0) or 0)
