"""Pallas TPU kernel: decode attention over an SMS-paged KV pool.

The XLA fallback (`ref.py`) must `take_along_axis` the entire pool into
logical order — a full extra cache copy per step (dominates the decode
memory roofline term; see EXPERIMENTS.md §Perf). This kernel instead
walks the block table with scalar-prefetched indices: page i's physical
slot is known before the grid step, so the pipeline DMAs exactly one
(ps, K, hd) page per step from HBM to VMEM and accumulates online
softmax in VMEM scratch. Cache reads become one pass, no copy.

TPU adaptation notes (DESIGN.md §2): this is the ServerlessMemory
"chunk" read path — pages are chunks, the block table is the daemon's
chunk->slab mapping, and PlaceChunk-compacted pages stay contiguous in
the pool so the DMA stream stays dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, ps: int, num_pages: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                 # (K, G, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (ps, K, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    K, G, hd = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("kgd,pkd->kgp", q, k,
                   preferred_element_type=jnp.float32) * scale
    pos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    valid = pos < len_ref[b]
    s = jnp.where(valid, s, -1e30)

    m_prev = m_ref[...]                              # (K, G)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[..., None]
                    + jnp.einsum("kgp,pkd->kgd", p, v,
                                 preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(i == num_pages - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_table, lens, *,
                                  interpret: bool):
    """q: (B, H, hd); pools: (B, P, ps, K, hd); block_table: (B, P) int32;
    lens: (B,) int32. Returns (B, H, hd) in q.dtype."""
    B, H, hd = q.shape
    _, P, ps, K, hd2 = k_pool.shape
    assert hd == hd2 and H % K == 0
    G = H // K
    q5 = q.reshape(B, K, G, hd)

    grid = (B, P)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, i, tbl, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, ps, K, hd),
                         lambda b, i, tbl, ln: (b, tbl[b, i], 0, 0, 0)),
            pl.BlockSpec((1, 1, ps, K, hd),
                         lambda b, i, tbl, ln: (b, tbl[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, i, tbl, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G, hd), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, num_pages=P),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(block_table, lens, q5, k_pool, v_pool)
    return out.reshape(B, H, hd)
