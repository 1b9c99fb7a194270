"""Paged decode-attention kernel vs jnp oracle — shape/dtype sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_ref


def _case(seed, B, P, ps, K, G, hd, dtype):
    H = K * G
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (B, P, ps, K, hd), dtype)
    vp = jax.random.normal(ks[2], (B, P, ps, K, hd), dtype)
    tbl = jnp.stack([jax.random.permutation(jax.random.fold_in(ks[3], b), P)
                     for b in range(B)]).astype(jnp.int32)
    lens = (jax.random.randint(jax.random.fold_in(ks[3], 99),
                               (B,), 1, P * ps + 1)).astype(jnp.int32)
    return q, kp, vp, tbl, lens


@pytest.mark.parametrize("B,P,ps,K,G,hd", [
    (1, 2, 4, 1, 1, 8),
    (2, 4, 8, 2, 2, 16),
    (3, 5, 8, 2, 3, 16),
    (2, 8, 16, 4, 1, 32),
])
def test_matches_ref_f32(B, P, ps, K, G, hd):
    args = _case(B * 100 + P, B, P, ps, K, G, hd, jnp.float32)
    want = np.asarray(paged_decode_attention_ref(*args))
    got = np.asarray(paged_decode_attention_pallas(*args, interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_matches_ref_bf16():
    args = _case(7, 2, 4, 8, 2, 2, 16, jnp.bfloat16)
    want = np.asarray(paged_decode_attention_ref(*args), dtype=np.float32)
    got = np.asarray(paged_decode_attention_pallas(*args, interpret=True),
                     dtype=np.float32)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


def test_permutation_invariance():
    """Physical page placement must not affect the result — the SMS
    compaction guarantee."""
    q, kp, vp, tbl, lens = _case(11, 2, 6, 4, 2, 2, 16, jnp.float32)
    out1 = paged_decode_attention_pallas(q, kp, vp, tbl, lens,
                                         interpret=True)
    # apply a permutation to physical pages + table
    perm = jax.random.permutation(jax.random.PRNGKey(5), 6)
    inv = jnp.argsort(perm)
    kp2 = kp[:, perm]
    vp2 = vp[:, perm]
    tbl2 = inv[tbl]
    out2 = paged_decode_attention_pallas(q, kp2, vp2, tbl2, lens,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-5, rtol=1e-5)


def test_pallas_backend_requires_tpu():
    """backend="pallas" means compiled: off a TPU it raises."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    args = _case(3, 1, 2, 4, 1, 1, 8, jnp.float32)
    with pytest.raises(RuntimeError, match="TPU"):
        paged_decode_attention(*args, backend="pallas")
