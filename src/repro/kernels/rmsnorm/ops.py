"""Public op: fused RMSNorm with backend dispatch."""
from __future__ import annotations

from repro.kernels.platform import on_tpu, require_tpu
from repro.kernels.rmsnorm.kernel import rms_norm_pallas
from repro.kernels.rmsnorm.ref import rms_norm_ref


def rms_norm_op(x, scale, eps: float = 1e-6, *, backend: str = "auto"):
    """backend: "pallas" (compiled; TPU only), "interpret" (Pallas
    interpreter), "ref" (XLA), "auto" (pallas on TPU else ref)."""
    if backend == "auto":
        backend = "pallas" if on_tpu() else "ref"
    if backend == "pallas":
        require_tpu("rms_norm_op")
        return rms_norm_pallas(x, scale, eps, interpret=False)
    if backend == "interpret":
        return rms_norm_pallas(x, scale, eps, interpret=True)
    return rms_norm_ref(x, scale, eps)
