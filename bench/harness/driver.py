"""The one general traffic generator: it reads a traffic mix (a data file
under `bench/traffic/`) and drives the store through its frontend.

Work is a fixed schedule drawn from the seed. Requests come in rounds;
each round holds the same operations, sizes and keys for every seed (the
mix, the size law and the key law read at evenly spaced strata), in
an order the seed shuffles. The shuffle keeps the round's skeleton, one
fixed sequence of (kind, half-octave size band): the seed only swaps
requests of one kind and band among that band's places. So seeds change
the order of the work and its bytes, never its amount, nor where in a
round the large objects queue up, which would move the tails.

Kinds of traffic:
- `closed_loop`: `clients` threads, each with one request outstanding;
  operations `put` (a fresh key), `update` (a new version of a working-
  set key) and `get` (a working-set key);
- `checkpoint`: one trainer that saves the model state back to back
  (`save`), the step cycling through `step_ring` steps; each save's
  state is made new on the chip, as a training step would make it.

The window: clients issue requests until `seconds` have passed and the
window closes when the last one issued has finished, so every request
that starts in the window counts whole, with its whole latency.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.harness import sizelaw, zipf

OFFSETS = 1 << 20            # distinct starting offsets in the pool
MASK64 = (1 << 64) - 1
SKELETON_SEED = 0x0DE5C0DE   # fixed: the rounds' skeleton is not seeded


def mix64(*xs: int) -> int:
    """splitmix64 over a tuple of integers: a stable hash for offsets and
    samples."""
    h = 0x9E3779B97F4A7C15
    for x in xs:
        h = (h ^ (x & MASK64)) & MASK64
        h = (h + 0x9E3779B97F4A7C15) & MASK64
        h = ((h ^ (h >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
        h = ((h ^ (h >> 27)) * 0x94D049BB133111EB) & MASK64
        h ^= h >> 31
    return h


class PayloadPool:
    """One seeded buffer of random bytes; the payload of (key, version
    generation) is a read-only view at an offset hashed from both, so
    generating it costs nothing and the oracle can rebuild it."""

    def __init__(self, seed: int, max_size: int):
        self.seed = seed & MASK64
        rng = np.random.default_rng([self.seed, 0x9001])
        self.buf = np.frombuffer(rng.bytes(max_size + OFFSETS), np.uint8)

    def content(self, key_id: int, gen: int, size: int) -> np.ndarray:
        off = mix64(self.seed, key_id, gen) % OFFSETS
        return self.buf[off:off + size]


def key_name(key_id: int) -> str:
    return f"obj/{key_id:08d}"


def working_set(law: Dict, dataset_bytes: int) -> List[int]:
    """Sizes of the working-set keys, by key id: the law's n strata, n the
    least that holds `dataset_bytes`."""
    n = max(1, int(dataset_bytes // sizelaw.mean_size(**law)))
    while sum(sizelaw.stratum_sizes(n, **law)) < dataset_bytes:
        n += 1
    return sizelaw.stratum_sizes(n, **law)


def band(size: int) -> int:
    """Half-octave size band: sizes within a factor of 2 ** 0.5."""
    return int(2 * np.log2(max(size, 1)))


def _split(weights: Dict[str, float], count: int) -> List[str]:
    """`count` names in proportion to `weights`, at mid-quantiles."""
    names = sorted(weights)
    cdf = np.cumsum([weights[n] for n in names], dtype=np.float64)
    cdf /= cdf[-1]
    u = (np.arange(count) + 0.5) / count
    return [names[i] for i in np.searchsorted(cdf, u, side="right")]


class Schedule:
    """Operation i of the run, as (kind, key id, size). Rounds are drawn
    lazily; thread-safe."""

    def __init__(self, traffic: Dict, law: Dict, seed: int,
                 key_sizes: List[int]):
        self.round = int(traffic.get("round", 1))
        kinds = _split(traffic["mix"], self.round)
        self.counts = {k: kinds.count(k) for k in set(kinds)}
        self.put_sizes = sizelaw.stratum_sizes(
            self.counts.get("put", 0), body_only=traffic.get("sizes")
            == "body", **law) if self.counts.get("put") else []
        self.key_sizes = key_sizes
        n = len(key_sizes)
        pmf = zipf.zipf_pmf(n, zipf.YCSB_ZIPFIAN_CONSTANT) if n else None
        scr = zipf.scramble(n) if n else None
        self.key_ops = {
            kind: [int(scr[r]) for r in zipf.stratified_ranks(pmf, c)]
            for kind, c in self.counts.items() if kind in ("get", "update")}
        self.first_fresh = n
        self._rng = np.random.default_rng([seed & MASK64, 0x5CED])
        ops = [("put", -1, int(size)) for size in self.put_sizes]
        for kind, keys in sorted(self.key_ops.items()):
            ops += [(kind, key, key_sizes[key]) for key in keys]
        order = np.random.default_rng(SKELETON_SEED).permutation(len(ops))
        self._skeleton = [ops[i] for i in order]
        self._places: Dict[Tuple[str, int], List[int]] = {}
        for place, (kind, _, size) in enumerate(self._skeleton):
            self._places.setdefault((kind, band(size)), []).append(place)
        self._ops: List[Tuple[str, int, int]] = []
        self._puts = 0
        self._lock = threading.Lock()

    def max_size(self) -> int:
        return max(self.put_sizes + self.key_sizes + [1])

    def _draw_round(self) -> None:
        ops = list(self._skeleton)
        for places in self._places.values():
            for dst, src in zip(places, self._rng.permutation(places)):
                ops[dst] = self._skeleton[src]
        for kind, key, size in ops:
            if kind == "put":
                key = self.first_fresh + self._puts
                self._puts += 1
            self._ops.append((kind, key, size))

    def op(self, i: int) -> Tuple[str, int, int]:
        with self._lock:
            while i >= len(self._ops):
                self._draw_round()
            return self._ops[i]


@dataclass
class Op:
    kind: str
    key: int
    gen: int
    size: int
    t0: float
    t1: float = float("nan")
    ok: bool = False
    version: Optional[int] = None


@dataclass
class Window:
    t_start: float
    t_end: float
    ops: List[Op]
    # held for the check after the window: sampled GET answers, or the
    # newest acked save and its state
    held: List[Tuple[Op, object]] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


class Versions:
    """Per working-set key: the generations issued and acked, with the
    time each was issued and acked and the store's version. Thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self.issued: Dict[int, List[Tuple[int, float]]] = {}
        self.acked: Dict[int, List[Tuple[int, float, int]]] = {}

    def preload(self, key: int, version: int) -> None:
        self.issued[key] = [(0, -1.0)]
        self.acked[key] = [(0, -1.0, version)]

    def bump(self, key: int, t: float) -> int:
        with self._lock:
            gens = self.issued.setdefault(key, [])
            gen = len(gens)
            gens.append((gen, t))
            return gen

    def ack(self, key: int, gen: int, t: float, version: int) -> None:
        with self._lock:
            self.acked.setdefault(key, []).append((gen, t, version))

    def latest(self, key: int) -> Tuple[int, int]:
        """(generation, version) of the newest acked write."""
        gen, _, ver = max(self.acked[key])
        return gen, ver

    def allowed(self, key: int, t0: float, t1: float) -> List[int]:
        """Generations a GET issued at t0 and answered at t1 may return:
        the newest acked before t0, or any issued before t1 after it."""
        with self._lock:
            done = [g for g, ta, _ in self.acked.get(key, []) if ta <= t0]
            base = max(done) if done else 0
            return sorted({base} | {g for g, ti in self.issued.get(key, [])
                                    if g > base and ti <= t1})


class HeldAnswers:
    """GET answers kept for the check after the window: GET i is kept
    where a hash of (seed, i) is a multiple of `every`. Whenever the kept
    answers pass `budget` bytes, `every` doubles and those whose hash is
    no multiple of it are let go, so at any time the kept answers are the
    same uniform sample, drawn from the seed, of every GET answered so
    far: the late part of the window is sampled as densely as the start.
    `every` starts at a power of two. Thread-safe."""

    def __init__(self, seed: int, every: int, budget: int):
        if every < 1 or every & (every - 1):
            raise ValueError(f"every={every} is not a power of two")
        self.seed, self.every, self.budget = seed, every, budget
        self._kept: List[Tuple[int, Op, object]] = []
        self._bytes = 0
        self._lock = threading.Lock()

    def offer(self, i: int, op: Op, val) -> None:
        h = mix64(self.seed, i, 0x401D)
        with self._lock:
            if h % self.every:
                return
            self._kept.append((h, op, val))
            self._bytes += op.size
            while self._bytes > self.budget:
                self.every *= 2
                self._kept = [kv for kv in self._kept
                              if kv[0] % self.every == 0]
                self._bytes = sum(op.size for _, op, _ in self._kept)

    def items(self) -> List[Tuple[Op, object]]:
        with self._lock:
            return [(op, val) for _, op, val in self._kept]


def closed_loop(store, pool: PayloadPool, schedule: Schedule,
                versions: Versions, *, clients: int, seconds: float,
                held: HeldAnswers, annotate: Callable[[str], object]
                ) -> Window:
    """Drive `clients` closed-loop clients for `seconds`; see the module
    docstring. `held` keeps a sample of the GET answers for the check
    after the window."""
    counter = itertools.count()
    ops: List[Op] = []
    t_start = time.perf_counter()
    t_stop = t_start + seconds

    def client() -> None:
        while time.perf_counter() < t_stop:
            i = next(counter)
            kind, key, size = schedule.op(i)
            gen = 0
            t0 = time.perf_counter()
            if kind == "update":
                gen = versions.bump(key, t0)
            op = Op(kind, key, gen, size, t0)
            try:
                with annotate(f"bench.{kind}"):
                    if kind == "get":
                        val = store.get_async(key_name(key)).result()
                    else:
                        val = store.put_async(
                            key_name(key),
                            pool.content(key, gen, size)).result()
                op.t1 = time.perf_counter()
                if kind == "get":
                    op.ok = val is not None
                    if op.ok:
                        held.offer(i, op, val)
                else:
                    op.ok = isinstance(val, int) and val >= 0
                    op.version = val
                    if op.ok and kind == "update":
                        versions.ack(key, gen, op.t1, val)
            except Exception:                      # counted as failed
                op.t1 = time.perf_counter()
            ops.append(op)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = max([t_start] + [op.t1 for op in ops])
    return Window(t_start, t_end, ops, held.items())


def checkpoint_loop(ckpt, params, stamp: Callable, nbytes: int, *,
                    seconds: float, step_ring: int,
                    annotate: Callable[[str], object]) -> Window:
    """Save back to back until `seconds` have passed; the last save
    started in the window ends it. Save i writes `stamp(params, i)` as
    step i % step_ring; the window holds the newest acked save's op and
    state for the check."""
    ops: List[Op] = []
    held: List[Tuple[Op, object]] = []
    t_start = time.perf_counter()
    t_stop = t_start + seconds
    i = 0
    while time.perf_counter() < t_stop:
        state = stamp(params, np.uint32(i))
        op = Op("save", i % step_ring, i, nbytes, time.perf_counter())
        try:
            with annotate("bench.save"):
                ckpt.save(op.key, state)
            op.ok = True
            held[:] = [(op, state)]
        except Exception:                          # counted as failed
            pass
        op.t1 = time.perf_counter()
        ops.append(op)
        i += 1
    return Window(t_start, ops[-1].t1 if ops else t_start, ops, held)
