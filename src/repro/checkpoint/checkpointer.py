"""InfiniStore-backed distributed checkpointing (DESIGN.md §2.2).

Train state leaves ride the store's zero-copy Payload path: each leaf
(device `jax.Array` or host numpy) becomes ONE host transfer + a flat
uint8 view that is fragmented, RS-erasure-coded, and PUT through the
InfiniStore data path — no intermediate `bytes` serialization. The SMS
tier (host-RAM slabs of DP peers) gives fast restore, the COS tier
(disk) gives durability, insertion logs give term-stamped failure
detection, and parallel recovery restores a lost host's chunks without a
full COS read.

Persistent-buffer semantics (§5.3.2): `save()` returns once SMS accepted
every shard — COS writes drain from the background writeback queue, and
shard batches ride `put_many_async` (one multi-key CAS round per batch)
so the next batch's host transfer overlaps the previous batch's encode.
An instance failure between save() and writeback completion loses
nothing: restore reads unpersisted chunks from the pending map.

Elastic restart: leaves are stored whole (per-leaf chunks), so restoring
onto a different DP width just re-shards at jit boundary — exercised by
tests/test_checkpoint.py.
"""
from __future__ import annotations

import json
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.payload import as_u8
from repro.core.store import StoreFrontend
from repro.obs import NOOP_CM

PyTree = Any


@dataclass
class CheckpointConfig:
    prefix: str = "ckpt"
    keep: int = 3                     # retained checkpoints
    leaf_shard_bytes: int = 64 * 1024 * 1024   # split huge leaves
    max_inflight_batches: int = 2     # pipelined async PUT batches


def _leaf_paths(tree: PyTree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out.append((name, leaf))
    return out


def _restore_dtype(name: str):
    if name == "bfloat16":
        return jax.numpy.bfloat16
    return np.dtype(name)


class Checkpointer:
    """Works over any `StoreFrontend` — the singleton `InfiniStore` or
    the keyspace-partitioned `ShardedStore`. Under a sharded store the
    ordered `.../sN` shard keys scatter by the router, so save batches
    fan out across every shard daemon (one multi-key CAS round per
    shard per sub-batch, leader-sequenced when a batch spans shards)
    and restores gather in parallel from all of them."""

    def __init__(self, store: StoreFrontend,
                 cfg: CheckpointConfig = CheckpointConfig()):
        self.store = store
        self.cfg = cfg
        self._saved_steps: List[int] = []
        self._lock = threading.Lock()

    # ---- save -------------------------------------------------------------

    def _manifest_key(self, step: int) -> str:
        return f"{self.cfg.prefix}/manifest/{step:08d}"

    def save(self, step: int, state: PyTree) -> None:
        # the store's ObsPlane, if it has one: a "ckpt.save" span with
        # the stages "ckpt.d2h" (each leaf's host copy) and "ckpt.wait"
        # (waits on the PUT batches)
        obs = getattr(self.store, "obs", None)
        with (obs.span("ckpt.save", step=step)
              if obs is not None else NOOP_CM) as span:
            nbytes = self._save(step, state, obs)
            if span is not None:
                span.tags["bytes"] = nbytes

    def _save(self, step: int, state: PyTree, obs) -> int:
        """`save`'s body; returns the bytes of the leaves saved."""
        leaves = _leaf_paths(state)
        total = 0
        manifest = {"step": step, "leaves": []}
        # shards ride pipelined async batched PUTs, flushed in bounded
        # sub-batches so peak host memory stays O(limit) (encode_many
        # materializes ~(k+p)/k x the sub-batch bytes) while keeping the
        # per-function invoke/log amortization within each sub-batch; at
        # most max_inflight_batches are outstanding at once
        limit = max(4 * self.cfg.leaf_shard_bytes, 64 * 1024 * 1024)
        sub, sub_bytes = [], 0
        inflight: List[Any] = []
        for name, leaf in leaves:
            # ONE device-to-host transfer per leaf; everything downstream
            # operates on this flat uint8 view (no bytes serialization)
            with (obs.span("ckpt.d2h") if obs is not None
                  else NOOP_CM) as span:
                u8 = as_u8(leaf)
                if span is not None:
                    span.tags["bytes"] = int(u8.size)
            total += int(u8.size)
            nshards = max(1, -(-u8.size // self.cfg.leaf_shard_bytes))
            for si in range(nshards):
                lo = si * self.cfg.leaf_shard_bytes
                hi = min(u8.size, lo + self.cfg.leaf_shard_bytes)
                sub.append((self._leaf_key(step, name, si), u8[lo:hi]))
                sub_bytes += hi - lo
                if sub_bytes >= limit:
                    inflight.append(self.store.put_many_async(sub))
                    sub, sub_bytes = [], 0
                    while len(inflight) >= self.cfg.max_inflight_batches:
                        with (obs.span("ckpt.wait") if obs is not None
                              else NOOP_CM):
                            inflight.pop(0).result()
            # dtype/shape come from the handle — no second host transfer
            dtype = getattr(leaf, "dtype", None)
            shape = getattr(leaf, "shape", None)
            if dtype is None or shape is None:    # python scalar leaf
                arr = np.asarray(leaf)
                dtype, shape = arr.dtype, arr.shape
            manifest["leaves"].append(
                {"name": name, "dtype": str(dtype),
                 "shape": list(shape), "nshards": nshards,
                 "nbytes": int(u8.size)})
        if sub:
            inflight.append(self.store.put_many_async(sub))
        with (obs.span("ckpt.wait") if obs is not None else NOOP_CM):
            for fut in inflight:
                fut.result()                     # SMS-accept barrier
        self.store.put(self._manifest_key(step),
                       json.dumps(manifest).encode())
        with self._lock:
            self._saved_steps.append(step)
            self._gc_old()
        return total

    def _leaf_key(self, step: int, name: str, shard: int) -> str:
        return f"{self.cfg.prefix}/{step:08d}/{name}/s{shard}"

    def _gc_old(self) -> None:
        while len(self._saved_steps) > self.cfg.keep:
            self._saved_steps.pop(0)
            # slabs age out via the GC window; COS retains durably

    # ---- restore -----------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        steps = []
        # cos_keys includes acked-but-not-yet-persisted manifests (the
        # pending writeback map), so a fresh save is always discoverable
        # chunk keys look like "chunk/<prefix>/manifest/<step>|<ver>/f0#N"
        # — the step sits in the second-to-last path component
        for key in self.store.cos_keys(
                f"chunk/{self.cfg.prefix}/manifest/"):
            try:
                steps.append(int(key.split("/")[-2].split("|")[0]))
            except (ValueError, IndexError):
                pass
        if self._saved_steps:
            steps.extend(self._saved_steps)
        return max(steps) if steps else None

    def restore(self, step: int, like: Optional[PyTree] = None) -> PyTree:
        mb = self.store.get(self._manifest_key(step))
        if mb is None:
            raise FileNotFoundError(f"no checkpoint manifest for {step}")
        manifest = json.loads(bytes(mb).decode())
        shard_keys = [self._leaf_key(step, entry["name"], si)
                      for entry in manifest["leaves"]
                      for si in range(entry["nshards"])]
        # batched array GETs in bounded sub-batches, mirroring save():
        # one unbounded get would hold ~3-4x the checkpoint in host RAM.
        # get_many_arrays returns flat uint8 views — leaves rebuild via
        # dtype/shape views, never through an intermediate bytes object.
        # Batches ride async futures with at most max_inflight_batches
        # outstanding, so batch i+1 queues on the client daemon while
        # batch i decodes — and the ordered .../sN shard keys let the
        # store's sequential-scan prefetcher warm the next shards' chunks
        # from COS during that decode (the degraded-restore fast path).
        limit = max(4 * self.cfg.leaf_shard_bytes, 64 * 1024 * 1024)
        per_batch = max(1, limit // self.cfg.leaf_shard_bytes)
        shards: Dict[str, Optional[np.ndarray]] = {}
        inflight: List[Any] = []
        for i in range(0, len(shard_keys), per_batch):
            inflight.append(self.store.get_many_arrays_async(
                shard_keys[i:i + per_batch]))
            while len(inflight) >= self.cfg.max_inflight_batches:
                shards.update(inflight.pop(0).result())
        for fut in inflight:
            shards.update(fut.result())
        leaves: Dict[str, np.ndarray] = {}
        for entry in manifest["leaves"]:
            parts = []
            for si in range(entry["nshards"]):
                a = shards.get(self._leaf_key(step, entry["name"], si))
                if a is None:
                    raise IOError(
                        f"checkpoint shard lost: {entry['name']}/s{si}")
                parts.append(a)
            u8 = parts[0] if len(parts) == 1 else np.concatenate(parts)
            arr = u8.view(_restore_dtype(entry["dtype"]))
            leaves[entry["name"]] = arr.reshape(entry["shape"])
        if like is None:
            return leaves
        named = _leaf_paths(like)
        flat = [leaves[name] for name, _ in named]
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, flat)
