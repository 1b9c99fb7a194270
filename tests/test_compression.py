"""int8 error-feedback gradient compression (optim/compression.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.optim import compression as C


def test_quantize_roundtrip_bounded_error():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, s = C.quantize_int8(x)
    xh = C.dequantize(q, s)
    assert q.dtype == jnp.int8
    # error bounded by half an LSB
    assert float(jnp.max(jnp.abs(x - xh))) <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates_to_truth():
    """Repeatedly compressing the SAME gradient with error feedback must
    converge: sum of transmitted values -> sum of true values."""
    g = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 0.01
    err = jnp.zeros_like(g)
    sent = jnp.zeros_like(g)
    for _ in range(20):
        xhat, err = C.compress_decompress(g + err)
        sent = sent + xhat
    np.testing.assert_allclose(np.asarray(sent / 20), np.asarray(g),
                               atol=1e-4)


def test_psum_compressed_single_pod_identity():
    """With one pod the compressed exchange must return ~the input."""
    from repro.launch.mesh import make_mesh, shard_map
    mesh = make_mesh((1,), ("pod",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (64,))}
    e = {"w": jnp.zeros((64,))}

    def f(g, e):
        return C.psum_compressed(g, "pod", e)

    out, new_e = shard_map(f, mesh=mesh, axis_names={"pod"},
                           in_specs=(P(), P()),
                           out_specs=(P(), P()))(g, e)
    np.testing.assert_allclose(np.asarray(out["w"] + new_e["w"]),
                               np.asarray(g["w"]), atol=1e-5)


def test_dcn_bytes_estimate():
    params = {"a": jnp.zeros((1000,)), "b": jnp.zeros((50, 50))}
    full = C.dcn_bytes_per_step(params, compressed=False)
    comp = C.dcn_bytes_per_step(params, compressed=True)
    assert full == 4 * 3500
    assert comp < full / 3.9        # ~4x reduction
