"""GF(256) arithmetic + pure-jnp oracle for the RS erasure-coding kernel.

Field: GF(2^8) with the AES/RS polynomial x^8+x^4+x^3+x^2+1 (0x11D),
generator 2. Host-side codec math (encode matrices, Gauss-Jordan
inversion) uses numpy tables; `gf256_matmul_ref` is the jnp oracle the
Pallas kernel is validated against.
"""
from __future__ import annotations

import numpy as np

try:
    import jax.numpy as jnp
except Exception:                                    # pragma: no cover
    jnp = None

POLY = 0x11D


def _build_tables():
    exp = np.zeros(512, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP_TABLE, LOG_TABLE = _build_tables()


def gf_mul_np(a, b):
    """Element-wise GF(256) multiply (numpy, table-based)."""
    a = np.asarray(a, np.int32)
    b = np.asarray(b, np.int32)
    out = EXP_TABLE[(LOG_TABLE[a] + LOG_TABLE[b]) % 255]
    return np.where((a == 0) | (b == 0), 0, out).astype(np.uint8)


def gf_inv_np(a):
    a = np.asarray(a, np.int32)
    if np.any(a == 0):
        raise ZeroDivisionError("GF(256) inverse of 0")
    return EXP_TABLE[255 - LOG_TABLE[a]].astype(np.uint8)


def gf_matmul_np(A: np.ndarray, X: np.ndarray) -> np.ndarray:
    """(m,k) @ (k,L) over GF(256): XOR-accumulated products."""
    A = np.asarray(A, np.uint8)
    X = np.asarray(X, np.uint8)
    m, k = A.shape
    out = np.zeros((m, X.shape[1]), np.uint8)
    for j in range(k):
        out ^= gf_mul_np(A[:, j:j + 1], X[j:j + 1, :])
    return out


def _build_mul_table() -> np.ndarray:
    """Full 256x256 GF(256) product table (64 KB): MUL[a, b] = a*b."""
    a = np.arange(256, dtype=np.uint8)
    return gf_mul_np(a[:, None], a[None, :])


GF_MUL_TABLE = _build_mul_table()


def gf_matmul_table(A: np.ndarray, X: np.ndarray) -> np.ndarray:
    """Fast-path (m,k) @ (k,L) over GF(256): one gather + one XOR per
    coefficient via the full product table, instead of the exp/log path's
    two gathers + add + mod + exp gather + zero masking. The codec's hot
    host matmul; `gf_matmul_np` stays as the independent oracle."""
    A = np.asarray(A, np.uint8)
    X = np.asarray(X, np.uint8)
    m, k = A.shape
    out = np.zeros((m, X.shape[1]), np.uint8)
    for i in range(m):
        row = out[i]
        for j in range(k):
            c = A[i, j]
            if c:
                row ^= GF_MUL_TABLE[c, X[j]]
    return out


def gf_inv_matrix_np(M: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inversion over GF(256)."""
    M = np.asarray(M, np.uint8)
    n = M.shape[0]
    aug = np.concatenate([M, np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = next((r for r in range(col, n) if aug[r, col] != 0), None)
        if piv is None:
            raise ValueError("singular GF(256) matrix")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        aug[col] = gf_mul_np(aug[col], gf_inv_np(aug[col, col]))
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= gf_mul_np(aug[r, col], aug[col])
    return aug[:, n:]


def cauchy_parity_matrix(k: int, p: int) -> np.ndarray:
    """Parity rows of a systematic RS code: Cauchy matrix
    C[i,j] = 1/(x_i ^ y_j) with x_i = k+i, y_j = j — every square
    submatrix of [I; C] is invertible, so any k of the k+p chunks
    reconstruct the data."""
    if k + p > 256:
        raise ValueError("k+p must be <= 256 for GF(256)")
    x = np.arange(k, k + p, dtype=np.int32)
    y = np.arange(k, dtype=np.int32)
    return gf_inv_np(x[:, None] ^ y[None, :])


# ---- jnp oracle ------------------------------------------------------------

def gf256_matmul_ref(G, X):
    """jnp oracle for the Pallas kernel: (m,k) @ (k,L) over GF(256),
    table-based."""
    exp = jnp.asarray(EXP_TABLE)
    log = jnp.asarray(LOG_TABLE)
    G = jnp.asarray(G, jnp.int32)
    X = jnp.asarray(X, jnp.int32)
    lg = log[G]                                  # (m,k)
    lx = log[X]                                  # (k,L)
    prod = exp[(lg[:, :, None] + lx[None, :, :]) % 255]
    prod = jnp.where((G[:, :, None] == 0) | (X[None, :, :] == 0), 0, prod)
    # XOR-reduce over k
    def xor_reduce(c, row):
        return c ^ row, None
    import jax
    out, _ = jax.lax.scan(lambda c, r: (c ^ r, None),
                          jnp.zeros((G.shape[0], X.shape[1]), jnp.int32),
                          jnp.moveaxis(prod, 1, 0))
    return out.astype(jnp.uint8)
