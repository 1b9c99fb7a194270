"""Arithmetic on the program's span records, as `repro.obs` exports them:
dicts with `site`, `span_id`, `parent_id`, `t0_us` (start on the
program's `perf_counter` clock), `dur_us`, `cpu_us` (the thread's CPU
time inside the span), `tid`, `pid` and `tags` (a byte count as
`bytes`).

A span's self time is its duration less the union of the intervals of
its children that ran on its own thread; children on other threads
(a writeback persist stitched under the PUT that queued it) run beside
it and cover none of its time. A record without the fields a quantity
needs, as an older program writes them, gives None.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

GiB = 1 << 30


def of(spans: Iterable[Dict], site: str) -> List[Dict]:
    """The spans of one site."""
    return [s for s in spans if s["site"] == site]


def seconds(spans: Sequence[Dict]) -> Optional[float]:
    """Summed duration in seconds; None where there is no span."""
    if not spans:
        return None
    return sum(s["dur_us"] or 0.0 for s in spans) * 1e-6


def tag_sum(spans: Sequence[Dict], tag: str) -> Optional[float]:
    """Sum of one tag over the spans; None where no span carries it."""
    vals = [s["tags"][tag] for s in spans if tag in s.get("tags", {})]
    return sum(vals) if vals else None


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_seconds(spans: Sequence[Dict], site: str) -> Optional[float]:
    """Summed self time, in seconds, of one site's spans; None where
    there is none or a record lacks its start or thread."""
    mine = of(spans, site)
    if not mine or any(s.get("t0_us") is None or s.get("tid") is None
                       or s["dur_us"] is None for s in mine):
        return None
    ids = {s["span_id"] for s in mine}
    kids: Dict[str, List[Dict]] = {}
    for s in spans:
        if s["parent_id"] in ids:
            kids.setdefault(s["parent_id"], []).append(s)
    total = 0.0
    for p in mine:
        lo, hi = p["t0_us"], p["t0_us"] + p["dur_us"]
        covered = [(max(c["t0_us"], lo), min(c["t0_us"] + c["dur_us"], hi))
                   for c in kids.get(p["span_id"], ())
                   if c.get("tid") == p["tid"] and c["pid"] == p["pid"]
                   and c.get("t0_us") is not None
                   and c["dur_us"] is not None]
        total += (hi - lo) - union_length(covered)
    return total * 1e-6


def cpu_share(spans: Sequence[Dict], site: str) -> Optional[float]:
    """Σ cpu_us / Σ dur_us of one site's spans, in percent; None where
    there is none or a record lacks its CPU time."""
    mine = of(spans, site)
    if not mine or any(s.get("cpu_us") is None for s in mine):
        return None
    wall = sum(s["dur_us"] or 0.0 for s in mine)
    if wall <= 0:
        return None
    return 100.0 * sum(s["cpu_us"] for s in mine) / wall


def per_gib(took: Optional[float], nbytes: int) -> Optional[float]:
    """Seconds per GiB of `nbytes`; None without either."""
    if took is None or not nbytes:
        return None
    return took / (nbytes / GiB)


def site_per_gib(run, site: str) -> Optional[float]:
    """Seconds in one site's spans per GiB of PUT payload acked."""
    return per_gib(seconds(of(run.spans, site)), run.put_bytes)
