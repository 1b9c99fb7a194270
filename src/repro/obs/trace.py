"""Lightweight per-op tracing: spans, ambient context, propagation.

A *span* is one timed stage of one operation: `trace_id` names the
whole operation (stable across threads, processes, and transport
epochs), `span_id` names this stage, `parent_id` stitches it under the
stage that caused it. Trace context is ambient — a thread-local
`(trace_id, span_id)` pair — so instrumentation never threads explicit
arguments through call chains:

- same thread: a nested `span()` reads the ambient pair and parents
  itself automatically;
- executor hop (client daemon, GET I/O pool, writeback writer, leader
  pool): the submitter captures `current()` and the task re-installs it
  with `use()` (see `ObsPlane.bind_current`);
- process hop: the parent attaches the pair to the RPC payload
  (`host._ShardProxy._rpc`) and the worker adopts it around dispatch,
  so worker-side spans carry the parent's `trace_id` across both the
  pipe and the TCP transport — including across reconnect epochs (the
  pair is plain data; a retransmitted frame carries the same trace).

Span ids are `<pid-hex>.<counter>` so ids never collide across worker
processes; trace ids are 64-bit random hex. Finished spans land in a
bounded ring (newest win) — collection is `ObsPlane`'s job.

A span record carries its start (`t0_us`, on the `perf_counter` clock
its `dur_us` uses), the CPU time its thread spent inside it (`cpu_us`,
from `time.thread_time()`) and the thread it ran on (`tid`), so a reader
can lay spans out on one time line and take a span's self time: its
duration less the union of its children on the same thread. A *stage*
span (a site in `sites.STAGE_SITES`: a leaf) also opens a
`jax.profiler.TraceAnnotation` of its name, so a profiler trace shows
which stage each host thread was in. jax is imported on the first such
span; the annotation starts no backend.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

# `jax.profiler`, imported on the first stage span (False: no jax)
_profiler = None

# span ids are `<pid-hex>.<n>`: the process id, refreshed in a forked
# child, and one counter per process
_pid = [os.getpid()]
_ids = itertools.count(1)
if hasattr(os, "register_at_fork"):
    os.register_at_fork(
        after_in_child=lambda: _pid.__setitem__(0, os.getpid()))

# ambient trace context: (trace_id, span_id) of the innermost open span
_tls = threading.local()


def current() -> Optional[Tuple[str, str]]:
    """The ambient (trace_id, span_id) pair, or None outside any span."""
    return getattr(_tls, "ctx", None)


class use:
    """Install a (trace_id, span_id) pair as the ambient context for a
    region — the adoption half of every propagation hop."""
    __slots__ = ("_ctx", "_prev")

    def __init__(self, ctx: Optional[Tuple[str, str]]):
        self._ctx = tuple(ctx) if ctx is not None else None

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc) -> bool:
        _tls.ctx = self._prev
        return False


def _annotation(site: str):
    """A `jax.profiler.TraceAnnotation` of the site, or None without
    jax. Looked up on every call, so a patched class takes effect."""
    global _profiler
    if _profiler is None:
        try:
            import jax.profiler as prof
        except ImportError:                  # pragma: no cover - no jax
            prof = False
        _profiler = prof
    return _profiler.TraceAnnotation(site) if _profiler else None


class Span:
    """One timed stage, and the context manager that times it: entering
    it opens it as a child of the ambient context and installs it as
    the ambient context for the body; leaving it records its duration
    and its thread's CPU time and hands it to the plane. A stage span
    holds a profiler annotation of its name over the same interval.
    `cpu0` (thread CPU seconds at the start) is None for a span recorded
    after the fact (`Tracer.since`)."""
    __slots__ = ("trace_id", "span_id", "parent_id", "site", "t0",
                 "dur_s", "cpu0", "cpu_s", "tid", "tags", "pid", "epoch",
                 "_plane", "_prev", "_ann")

    def __init__(self, plane, site: str, tags: Dict, ann=None):
        self._plane = plane
        self.site = site
        self.tags = tags
        self._ann = ann
        self.dur_s: Optional[float] = None
        self.cpu_s: Optional[float] = None

    def _open(self, parent: Optional[Tuple[str, str]]) -> None:
        if parent is None:
            self.trace_id = os.urandom(8).hex()
            self.parent_id = None
        else:
            self.trace_id, self.parent_id = parent
        self.pid = _pid[0]
        self.span_id = f"{self.pid:x}.{next(_ids)}"
        self.tid = threading.get_ident()
        self.epoch = self._plane.epoch

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        self._prev = parent = getattr(_tls, "ctx", None)
        self._open(parent)
        _tls.ctx = (self.trace_id, self.span_id)
        self.t0 = time.perf_counter()
        self.cpu0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.cpu_s = time.thread_time() - self.cpu0
        self.dur_s = time.perf_counter() - self.t0
        _tls.ctx = self._prev
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if exc_type is not None:
            self.tags["error"] = exc_type.__name__
        self._plane._finish_span(self)
        return False

    def to_dict(self, *, timeline: bool = True) -> Dict:
        """The span's record; `timeline=False` leaves out its start,
        CPU time and thread (the flight file's compact record)."""
        d = {"trace_id": self.trace_id, "span_id": self.span_id,
             "parent_id": self.parent_id, "site": self.site,
             "dur_us": None if self.dur_s is None
             else round(self.dur_s * 1e6, 1),
             "pid": self.pid, "epoch": self.epoch,
             "tags": dict(self.tags)}
        if timeline:
            d["t0_us"] = round(self.t0 * 1e6, 1)
            d["cpu_us"] = None if self.cpu_s is None \
                else round(self.cpu_s * 1e6, 1)
            d["tid"] = self.tid
        return d


class _Noop:
    """Shared no-op context manager: what `span()` hands out when the
    plane is disabled, so disabled sites cost one branch and no
    allocation."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


NOOP_CM = _Noop()


class Tracer:
    """Bounded ring of finished spans. Appends are a single GIL-atomic
    `deque.append` (maxlen evicts the oldest), so recording takes no
    lock — the same multi-writer discipline as `AtomicCounter`."""

    def __init__(self, capacity: int = 4096):
        self._ring: deque = deque(maxlen=capacity)

    def start(self, plane, site: str, tags: Dict,
              stage: bool = False) -> Span:
        return Span(plane, site, tags, _annotation(site) if stage else None)

    def since(self, plane, site: str, t0: float, tags: Dict) -> Span:
        """A span that began at `t0` (perf_counter seconds, perhaps on
        another thread) and ends now, under the ambient context. Its
        CPU time is not known: `cpu_us` is None."""
        span = Span(plane, site, tags)
        span._open(getattr(_tls, "ctx", None))
        span.t0 = t0
        span.cpu0 = None
        span.dur_s = time.perf_counter() - t0
        return span

    def add(self, span: Span) -> None:
        self._ring.append(span)

    def snapshot(self) -> List[Dict]:
        return [s.to_dict() for s in list(self._ring)]
