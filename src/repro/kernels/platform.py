"""Which platform a kernel backend may run on."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when this process's JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def require_tpu(op: str) -> None:
    """Raise unless this process's JAX backend is a TPU: backend="pallas"
    means the compiled kernel, never a quiet fall back to the Pallas
    interpreter."""
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"{op}: backend='pallas' runs the kernel compiled for a TPU, "
            f"but JAX's backend here is {platform!r}; use "
            f"backend='interpret' for the Pallas interpreter")
