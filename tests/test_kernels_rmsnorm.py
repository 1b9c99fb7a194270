"""Fused RMSNorm kernel vs oracle — shape/dtype sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.rmsnorm.kernel import rms_norm_pallas
from repro.kernels.rmsnorm.ref import rms_norm_ref


@pytest.mark.parametrize("shape", [(4, 128), (3, 7, 256), (1, 512),
                                   (300, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matches_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(shape)))
    x = jax.random.normal(k1, shape, dtype)
    scale = jax.random.normal(k2, shape[-1:], dtype) * 0.1 + 1.0
    want = np.asarray(rms_norm_ref(x, scale), np.float32)
    got = np.asarray(rms_norm_pallas(x, scale, interpret=True), np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_pallas_backend_requires_tpu():
    """backend="pallas" means compiled: off a TPU it raises."""
    from repro.kernels.rmsnorm.ops import rms_norm_op
    x = jnp.ones((4, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="TPU"):
        rms_norm_op(x, jnp.ones((128,), jnp.float32), backend="pallas")
