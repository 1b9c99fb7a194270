"""Reed-Solomon erasure-coding codec facade (paper: RS(10+2) by default).

Splits a byte payload into k data chunks + p parity chunks; any k of the
k+p chunks reconstruct the payload. The GF(256) matmul runs where the
process's platform says (`backend="auto"`, the default): the bit-sliced
Pallas kernel compiled on a TPU, numpy's full 256x256 product table (one
gather + one XOR per coefficient) everywhere else. `backend="pallas"`
demands the compiled kernel and raises without a TPU; `"interpret"` runs
the kernel in the Pallas interpreter; `"numpy"` pins the host table. All
are bit-identical (tests/test_kernels_rs.py, tests/test_ec.py).

Batched data path: `encode_many` / `decode_many` stack every fragment of
a request column-wise into ONE (k, sum L) GF(256) matmul instead of one
dispatch per fragment, and decode matrices are LRU-cached by survivor
index tuple so repeated degraded reads with the same survivor set pay
for exactly one O(k^3) Gauss-Jordan inversion (`cache_info()` exposes
hit accounting). Encode writes the framed payload straight into one
preallocated stacked buffer — no intermediate `header + payload` concat.
"""
from __future__ import annotations

import struct
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core.payload import as_u8, payload_nbytes
from repro.kernels.platform import on_tpu, require_tpu
from repro.kernels.rs_gf256.kernel import (gf256_matmul_bitsliced,
                                           warmup_bitsliced)
from repro.kernels.rs_gf256.ref import (cauchy_parity_matrix,
                                        gf_inv_matrix_np, gf_matmul_table)
from repro.obs import NOOP_CM

_HEADER = struct.Struct("<I")    # original length prefix


@dataclass(frozen=True)
class ECConfig:
    k: int = 10
    p: int = 2

    @property
    def n(self) -> int:
        return self.k + self.p


class RSCodec:
    def __init__(self, cfg: ECConfig = ECConfig(), *, backend: str = "auto",
                 inv_cache_size: int = 64, obs=None):
        self.cfg = cfg
        # optional ObsPlane (repro.obs), the owning store's: "ec.frame"
        # spans here, "ec.dispatch" / "ec.collect" in the kernel wrapper
        self.obs = obs
        if backend == "auto":
            backend = "pallas" if on_tpu() else "numpy"
        if backend == "pallas":
            require_tpu("RSCodec")
        elif backend not in ("numpy", "interpret"):
            raise ValueError(f"unknown RSCodec backend {backend!r}")
        self.backend = backend
        self._parity = cauchy_parity_matrix(cfg.k, cfg.p)
        self._gen = np.concatenate(
            [np.eye(cfg.k, dtype=np.uint8), self._parity], axis=0)
        # decode-matrix LRU: survivor index tuple -> inverted (k, k) matrix
        self._inv_cache: "OrderedDict[Tuple[int, ...], np.ndarray]" = \
            OrderedDict()
        self._inv_cache_size = inv_cache_size
        self._inv_lock = threading.Lock()    # store serves concurrent GETs
        self._cache_hits = 0
        self._cache_misses = 0
        self._inversions = 0

    def _matmul(self, G: np.ndarray, X: np.ndarray) -> np.ndarray:
        if self.backend == "numpy":
            return gf_matmul_table(G, X)
        return gf256_matmul_bitsliced(
            G, X, interpret=self.backend == "interpret", obs=self.obs)

    def warmup(self) -> None:
        """Compile the kernel for every tile bucket of both geometries
        this codec uses — encode (p, k) and decode (k, k) — so serving
        afterwards compiles nothing. A no-op for the numpy backend."""
        if self.backend == "numpy":
            return
        for m in (self.cfg.p, self.cfg.k):
            warmup_bitsliced(m, self.cfg.k,
                             interpret=self.backend == "interpret")

    # ---- encode -------------------------------------------------------------

    def encode(self, payload: bytes) -> List[bytes]:
        """payload -> k+p chunk payloads (equal length)."""
        return self.encode_many([payload])[0]

    def encode_many(self, payloads: Sequence, *,
                    as_arrays: bool = False) -> List[List[bytes]]:
        """Batch encode: all payloads' data blocks are stacked column-wise
        into one (k, sum clen) buffer and the parity rows come from a
        single GF(256) matmul.

        Payloads may be bytes OR array-like (numpy / jax uint8 views via
        the Payload protocol) — device-backed fragments reach the kernel
        without an intermediate `bytes` copy. With `as_arrays=True`
        chunks come back as uint8 views into the stacked encode buffer
        (zero-copy) instead of materialized `bytes`."""
        if not payloads:
            return []
        k, p = self.cfg.k, self.cfg.p
        clens = [self.chunk_len(payload_nbytes(pl)) for pl in payloads]
        obs = self.obs
        with (obs.span("ec.frame", bytes=k * int(sum(clens)))
              if obs is not None else NOOP_CM):
            data = np.zeros((k, int(sum(clens))), np.uint8)
            off = 0
            for pl, clen in zip(payloads, clens):
                self._fill_framed(data[:, off:off + clen], as_u8(pl))
                off += clen
        parity = self._matmul(self._parity, data)
        out: List[List[bytes]] = []
        off = 0
        for clen in clens:
            sl = slice(off, off + clen)
            if as_arrays:
                out.append([data[i, sl] for i in range(k)] +
                           [parity[i, sl] for i in range(p)])
            else:
                out.append([data[i, sl].tobytes() for i in range(k)] +
                           [parity[i, sl].tobytes() for i in range(p)])
            off += clen
        return out

    @staticmethod
    def _fill_framed(block: np.ndarray, flat: np.ndarray) -> None:
        """Write the framed payload (length header + flat uint8 payload)
        row-major into `block` — a (k, clen) column-slice view of the
        stacked buffer — via direct per-row memcpys."""
        k, clen = block.shape
        hdr = np.frombuffer(_HEADER.pack(flat.size), np.uint8)
        H, end = hdr.size, hdr.size + flat.size
        for i in range(k):
            s = i * clen
            if s >= end:
                break
            e = min(s + clen, end)
            dst = block[i]
            if s < H:                          # row overlaps the header
                hn = min(H, e) - s
                dst[:hn] = hdr[s:s + hn]
                if e > H:
                    dst[hn:e - s] = flat[:e - H]
            else:
                dst[:e - s] = flat[s - H:e - H]

    # ---- decode -------------------------------------------------------------

    def decode(self, chunks: Dict[int, bytes]) -> bytes:
        """chunks: {chunk_index: payload} with >= k entries. Returns the
        original payload (any k of the k+p indices suffice)."""
        return self.decode_many([chunks])[0]

    def decode_many(self, chunk_maps: Sequence[Dict[int, bytes]], *,
                    as_arrays: bool = False) -> List[bytes]:
        """Batch decode: fragments sharing a survivor set are stacked
        column-wise and reconstructed by one cached-inverse matmul.

        Chunks may be bytes or uint8 arrays (slab-resident views). With
        `as_arrays=True` results are flat uint8 arrays — the GET-side
        zero-copy path (no `bytes` materialization per fragment)."""
        k = self.cfg.k
        ident = tuple(range(k))
        results: List = [b""] * len(chunk_maps)
        groups: Dict[Tuple[int, ...], List[int]] = {}
        for pos, chunks in enumerate(chunk_maps):
            if len(chunks) < k:
                raise ValueError(
                    f"need >= {k} chunks to decode, got {len(chunks)}")
            idx = tuple(sorted(chunks)[:k])
            if idx == ident:                   # all data rows survive
                if not as_arrays and all(isinstance(chunks[i], bytes)
                                         for i in ident):
                    results[pos] = self._unframe(
                        b"".join(chunks[i] for i in ident))
                else:
                    flat = np.concatenate([as_u8(chunks[i]) for i in ident])
                    results[pos] = self._unframe_np(flat, as_arrays)
            else:
                groups.setdefault(idx, []).append(pos)
        for idx, positions in groups.items():
            inv = self._decode_matrix(idx)
            clens = [payload_nbytes(chunk_maps[pos][idx[0]])
                     for pos in positions]
            surv = np.empty((k, int(sum(clens))), np.uint8)
            off = 0
            for pos, clen in zip(positions, clens):
                cm = chunk_maps[pos]
                for r, i in enumerate(idx):
                    surv[r, off:off + clen] = as_u8(cm[i])
                off += clen
            dec = self._matmul(inv, surv)
            off = 0
            for pos, clen in zip(positions, clens):
                flat = dec[:, off:off + clen].reshape(-1)
                results[pos] = self._unframe_np(flat, as_arrays)
                off += clen
        return results

    def _decode_matrix(self, idx: Tuple[int, ...]) -> np.ndarray:
        with self._inv_lock:
            inv = self._inv_cache.get(idx)
            if inv is not None:
                self._inv_cache.move_to_end(idx)
                self._cache_hits += 1
                return inv
            self._cache_misses += 1
            self._inversions += 1
        inv = gf_inv_matrix_np(self._gen[list(idx)])   # outside the lock
        with self._inv_lock:
            self._inv_cache[idx] = inv
            if len(self._inv_cache) > self._inv_cache_size:
                self._inv_cache.popitem(last=False)
        return inv

    @staticmethod
    def _unframe(framed: bytes) -> bytes:
        (orig_len,) = _HEADER.unpack_from(framed)
        return framed[_HEADER.size:_HEADER.size + orig_len]

    @staticmethod
    def _unframe_np(flat: np.ndarray, as_arrays: bool):
        """Unframe a flat uint8 buffer; returns a view (as_arrays) or
        bytes."""
        (orig_len,) = _HEADER.unpack_from(flat[:_HEADER.size].tobytes())
        body = flat[_HEADER.size:_HEADER.size + orig_len]
        return body if as_arrays else body.tobytes()

    def cache_info(self) -> Dict[str, int]:
        """Decode-matrix LRU accounting (hits/misses/inversions/size)."""
        return {"hits": self._cache_hits, "misses": self._cache_misses,
                "inversions": self._inversions,
                "size": len(self._inv_cache)}

    def chunk_len(self, payload_len: int) -> int:
        return -(-(payload_len + _HEADER.size) // self.cfg.k)
