"""Public op: paged decode attention with backend dispatch."""
from __future__ import annotations

from repro.kernels.platform import on_tpu, require_tpu
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref


def paged_decode_attention(q, k_pool, v_pool, block_table, lens, *,
                           backend: str = "auto"):
    """Decode attention over an SMS-paged KV pool.

    backend: "pallas" (compiled; TPU only),
             "interpret" (Pallas interpreter), "ref" (XLA gather fallback),
             "auto" (pallas on TPU else ref).
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "ref"
    if backend == "pallas":
        require_tpu("paged_decode_attention")
        return paged_decode_attention_pallas(q, k_pool, v_pool, block_table,
                                             lens, interpret=False)
    if backend == "interpret":
        return paged_decode_attention_pallas(q, k_pool, v_pool, block_table,
                                             lens, interpret=True)
    return paged_decode_attention_ref(q, k_pool, v_pool, block_table,
                                      lens).astype(q.dtype)
