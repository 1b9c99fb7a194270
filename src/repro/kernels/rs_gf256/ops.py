"""Public op: gf256_matmul with backend dispatch.

"pallas" is the bit-sliced kernel compiled for the TPU and raises on any
other platform; interpret mode runs only when asked for by name.
"""
from __future__ import annotations

from repro.kernels.platform import on_tpu, require_tpu
from repro.kernels.rs_gf256.kernel import gf256_matmul_bitsliced
from repro.kernels.rs_gf256.ref import gf256_matmul_ref


def gf256_matmul(G, X, *, backend: str = "auto"):
    """OUT = G @ X over GF(256). G: (m,k) uint8, X: (k,L) uint8.

    backend: "pallas" (bit-sliced kernel, compiled; TPU only),
             "interpret" (bit-sliced kernel in the Pallas interpreter),
             "ref" (jnp oracle), "auto" (pallas on TPU else ref).
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "ref"
    if backend == "pallas":
        require_tpu("gf256_matmul")
        return gf256_matmul_bitsliced(G, X, interpret=False)
    if backend == "interpret":
        return gf256_matmul_bitsliced(G, X, interpret=True)
    if backend == "ref":
        return gf256_matmul_ref(G, X)
    raise ValueError(f"unknown gf256_matmul backend {backend!r}")
