"""Kernel microbenchmarks: wall-time of the Pallas kernels (interpret
mode on CPU — correctness-path timing, NOT TPU performance) vs the
XLA/numpy references, plus work-per-call accounting."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row


def _time(fn, *args, n=3):
    fn(*args)            # compile/warm
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out) if hasattr(out, "block_until_ready") else None
    return (time.perf_counter() - t0) / n * 1e6


def run() -> list:
    out = []
    # RS encode: 1 MB payload through GF(256) matmul — exp/log numpy vs
    # product-table numpy vs the bit-sliced Pallas kernel
    from repro.kernels.rs_gf256.ref import (cauchy_parity_matrix,
                                            gf_matmul_np, gf_matmul_table)
    from repro.kernels.rs_gf256.kernel import gf256_matmul_bitsliced
    rng = np.random.default_rng(0)
    k, p, L = 10, 2, 104_858   # ~1MB/10 per chunk
    G = cauchy_parity_matrix(k, p)
    X = rng.integers(0, 256, (k, L)).astype(np.uint8)
    us_np = _time(lambda: gf_matmul_np(G, X))
    us_tab = _time(lambda: gf_matmul_table(G, X))
    us_bs = _time(lambda: gf256_matmul_bitsliced(G, X, interpret=True))
    out.append(row("kernel_rs_encode_numpy", us_np,
                   f"bytes={k * L} parity={p} exp/log path"))
    out.append(row("kernel_rs_encode_numpy_table", us_tab,
                   "full 256x256 product table (codec hot path)"))
    out.append(row("kernel_rs_encode_pallas_bitsliced", us_bs,
                   "bit-planes, 4 bytes/lane (CPU interpret, TPU target)"))
    # paged attention vs gather fallback
    from repro.kernels.paged_attention.kernel import \
        paged_decode_attention_pallas
    from repro.kernels.paged_attention.ref import paged_decode_attention_ref
    B, P, ps, K, G_, hd = 4, 16, 32, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, K * G_, hd))
    kp = jax.random.normal(ks[1], (B, P, ps, K, hd))
    vp = jax.random.normal(ks[2], (B, P, ps, K, hd))
    tbl = jnp.tile(jnp.arange(P, dtype=jnp.int32)[None], (B, 1))
    lens = jnp.full((B,), P * ps, jnp.int32)
    ref_fn = jax.jit(paged_decode_attention_ref)
    us_ref = _time(lambda: ref_fn(q, kp, vp, tbl, lens))
    us_pal = _time(lambda: paged_decode_attention_pallas(
        q, kp, vp, tbl, lens, interpret=True))
    cache_bytes = 2 * B * P * ps * K * hd * 4
    out.append(row("kernel_paged_attn_xla_gather", us_ref,
                   f"cache={cache_bytes // 1024}KB gather-copies=1"))
    out.append(row("kernel_paged_attn_pallas_interpret", us_pal,
                   "zero-copy page walk (TPU target)"))
    return out
