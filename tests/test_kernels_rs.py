"""Pallas GF(256) matmul kernel vs jnp oracle vs numpy — shape sweep."""
import numpy as np
import pytest

from repro.kernels.rs_gf256.kernel import gf256_matmul_bitsliced
from repro.kernels.rs_gf256.ref import (cauchy_parity_matrix,
                                        gf256_matmul_ref, gf_matmul_np,
                                        gf_mul_np, gf_inv_np)


def test_field_axioms():
    a = np.arange(1, 256, dtype=np.uint8)
    assert np.all(gf_mul_np(a, gf_inv_np(a)) == 1)
    # distributivity over a sample
    rng = np.random.default_rng(0)
    x, y, z = (rng.integers(0, 256, 100).astype(np.uint8) for _ in range(3))
    assert np.all(gf_mul_np(x, y ^ z) == (gf_mul_np(x, y) ^ gf_mul_np(x, z)))


@pytest.mark.parametrize("m,k", [(2, 10), (4, 4), (1, 2), (6, 12)])
@pytest.mark.parametrize("L", [1, 100, 1024, 2048 + 77])
def test_kernel_matches_oracle(m, k, L):
    rng = np.random.default_rng(m * 1000 + k * 10 + L)
    G = rng.integers(0, 256, (m, k)).astype(np.uint8)
    X = rng.integers(0, 256, (k, L)).astype(np.uint8)
    want = gf_matmul_np(G, X)
    ref = np.asarray(gf256_matmul_ref(G, X))
    pal = gf256_matmul_bitsliced(G, X, interpret=True)
    assert np.array_equal(ref, want)
    assert np.array_equal(pal, want)


def test_cauchy_rows_invertible_property():
    """Every k x k submatrix of [I; C] must be invertible (MDS)."""
    from itertools import combinations
    from repro.kernels.rs_gf256.ref import gf_inv_matrix_np
    k, p = 4, 2
    G = np.concatenate([np.eye(k, dtype=np.uint8),
                        cauchy_parity_matrix(k, p)], 0)
    for rows in combinations(range(k + p), k):
        gf_inv_matrix_np(G[list(rows)])   # raises if singular


def test_rows_longer_than_one_tile():
    """A row wider than MAX_TILE is cut into column tiles (the last one
    padded to its bucket) and stitched back bit-identically."""
    from repro.kernels.rs_gf256.kernel import MAX_TILE, column_tiles
    from repro.kernels.rs_gf256.ref import gf_matmul_table
    rng = np.random.default_rng(3)
    L = 2 * MAX_TILE + 4099
    G = rng.integers(0, 256, (2, 3)).astype(np.uint8)
    X = rng.integers(0, 256, (3, L)).astype(np.uint8)
    assert [w for _, w, _ in column_tiles(L)] == [MAX_TILE, MAX_TILE, 4099]
    assert np.array_equal(gf256_matmul_bitsliced(G, X, interpret=True),
                          gf_matmul_table(G, X))


def test_pallas_backend_requires_tpu():
    """backend="pallas" is the compiled kernel: without a TPU it raises
    rather than quietly running the Pallas interpreter."""
    from repro.kernels.rs_gf256.ops import gf256_matmul
    G = np.ones((2, 3), np.uint8)
    X = np.ones((3, 10), np.uint8)
    with pytest.raises(RuntimeError, match="TPU"):
        gf256_matmul(G, X, backend="pallas")
    assert np.array_equal(gf256_matmul(G, X, backend="interpret"),
                          gf_matmul_np(G, X))
