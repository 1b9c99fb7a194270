"""Pallas TPU kernel: GF(256) matrix multiply for Reed-Solomon coding.

Computes OUT = G ∘ X over GF(2^8): OUT[i, :] = XOR_j gfmul(G[i,j], X[j, :]).
Used for both EC encode (G = Cauchy parity rows, m = p) and decode
(G = inverted reconstruction matrix, m = k).

DESIGN (bit-sliced)
-------------------
GPU RS codecs use shared-memory log/exp tables; TPU VMEM has no efficient
gather, so the multiply must decompose into vector ALU ops. Multiplication
by a *constant* c is GF(2)-linear in the bits of x, i.e. an 8x8 bit matrix
(the companion-matrix representation of c). We exploit that in three ways:

1. **Bit-plane coefficients** — each coefficient G[i,j] expands to 8 bytes
   ``plane[b] = gfmul(G[i,j], 2^b)`` (the image of input bit b), computed
   inside the jitted program by an xtime ladder over G and replicated into
   all four bytes of a uint32. They sit in SMEM as one flat (m*k*8,)
   vector. The inner loop is then pure mask/XOR accumulation:
   ``out ^= spread(bit_b(x)) & plane[b]`` with no per-bit selects and no
   data-dependent control flow.
2. **4 bytes per int32 lane** — X travels as uint32 words (a free host
   view of the uint8 rows), so every VPU lane carries 4 payload bytes.
   ``bits = (x >> b) & 0x01010101`` grabs bit b of all four bytes at once
   and ``(bits << 8) - bits`` spreads each 0/1 byte to 0x00/0xFF
   (byte-local borrow, no cross-byte carries). Each byte is computed on
   its own, so the host's byte order never matters.
3. **Grid over column blocks, all output rows per step** — X is laid out
   (k, rows, 128) and OUT (m, rows, 128): one grid step owns a
   (k, BLOCK_ROWS, 128) block of every input row and writes the matching
   (m, BLOCK_ROWS, 128) block of every output row, so each block's last
   two dims are (8n, 128) as the TPU requires. Inside a step a loop walks
   (8, 128) vreg tiles: each input tile's 8 bit masks are built once and
   reused by all m accumulators, which stay in registers.

Shapes are bounded: a row of L bytes is processed in column tiles of at
most MAX_TILE bytes, each padded on the host to a power-of-two bucket
between MIN_TILE and MAX_TILE (`TILE_BUCKETS`). One compiled program per
(m, k, bucket) serves every payload length, so a warm store compiles
nothing however its object sizes mix. Validated bit-identical to the
numpy/jnp oracles in interpret mode on CPU; compiled on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 128
BLOCK_ROWS = 256                 # sublane rows of 128 words per grid step
_ROW_BYTES = 4 * LANES           # bytes in one (1, 128) uint32 row
MIN_TILE = SUBLANES * _ROW_BYTES  # 4 KiB: one (8, 128) uint32 vreg per row
MAX_TILE = 4 * 1024 * 1024       # column tile cap (bytes per data row)
TILE_BUCKETS = tuple(MIN_TILE << i
                     for i in range((MAX_TILE // MIN_TILE).bit_length()))

_LOW_BITS = 0x01010101   # bit 0 of each packed byte


def _rs_bitsliced_kernel(g_ref, x_ref, o_ref, *, m: int, k: int):
    """g_ref: (m*k*8,) uint32 byte-replicated planes in SMEM; x_ref:
    (k, R, 128) uint32 data block; o_ref: (m, R, 128) uint32."""
    low = jnp.uint32(_LOW_BITS)

    def tile(r, carry):
        rows = pl.ds(pl.multiple_of(r * SUBLANES, SUBLANES), SUBLANES)
        acc = [jnp.zeros((SUBLANES, LANES), jnp.uint32) for _ in range(m)]
        for j in range(k):
            xj = x_ref[j, rows, :]
            for b in range(8):
                bits = (xj >> b) & low
                mask = (bits << 8) - bits          # 0x00/0xFF per byte
                for i in range(m):
                    acc[i] = acc[i] ^ (mask & g_ref[(i * k + j) * 8 + b])
        for i in range(m):
            o_ref[i, rows, :] = acc[i]
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // SUBLANES, tile, 0)


def _coeff_planes(G: jax.Array) -> jax.Array:
    """(m, k) uint8 -> (m*k*8,) uint32: plane b of G[i,j] is G[i,j]*2^b
    over GF(256) (xtime ladder, poly 0x11D) — the image of input bit b,
    one column of the coefficient's 8x8 GF(2) companion matrix —
    replicated into all 4 bytes of the word."""
    c = G.astype(jnp.uint32)
    planes = []
    for _ in range(8):
        planes.append(c)
        c = ((c << 1) & 0xFF) ^ jnp.where((c & 0x80) != 0,
                                          jnp.uint32(0x1D), jnp.uint32(0))
    return (jnp.stack(planes, axis=-1) * jnp.uint32(_LOW_BITS)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _matmul_tile(G: jax.Array, Xw: jax.Array, *, interpret: bool
                 ) -> jax.Array:
    """One bucket-wide column tile: G (m, k) uint8, Xw (k, rows, 128)
    uint32 -> (m, rows, 128) uint32. Planes and kernel in one program."""
    m, k = G.shape
    rows = Xw.shape[1]
    block = min(rows, BLOCK_ROWS)
    return pl.pallas_call(
        functools.partial(_rs_bitsliced_kernel, m=m, k=k),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # planes
            pl.BlockSpec((k, block, LANES), lambda w: (0, w, 0)),  # data
        ],
        out_specs=pl.BlockSpec((m, block, LANES), lambda w: (0, w, 0)),
        out_shape=jax.ShapeDtypeStruct((m, rows, LANES), jnp.uint32),
        name="rs_gf256_bitsliced",
        interpret=interpret,
    )(_coeff_planes(G), Xw)


def tile_bucket(width: int) -> int:
    """Smallest bucket holding `width` bytes (width <= MAX_TILE)."""
    return max(MIN_TILE, 1 << (max(width, 1) - 1).bit_length())


def column_tiles(L: int):
    """(offset, width, bucket) of each column tile of an L-byte row."""
    return [(off, min(MAX_TILE, L - off), tile_bucket(min(MAX_TILE, L - off)))
            for off in range(0, L, MAX_TILE)]


def gf256_matmul_bitsliced(G, X, *, interpret: bool) -> np.ndarray:
    """Bit-sliced GF(256) matmul. G: (m,k) uint8, X: (k,L) uint8 ->
    (m, L) uint8 numpy, bit-identical to `gf_matmul_table`.

    The host cuts X into MAX_TILE-wide column tiles, pads each into a
    zeroed bucket-wide buffer and reinterprets it as uint32 words (a view,
    not a pass over the data). Every tile is dispatched before the first
    result is read, so transfers and kernels of successive tiles
    overlap."""
    G8 = np.asarray(G, np.uint8)
    X8 = np.asarray(X, np.uint8)
    m, k = G8.shape
    L = X8.shape[1]
    pending = []
    for off, w, bucket in column_tiles(L):
        tile = np.zeros((k, bucket), np.uint8)
        tile[:, :w] = X8[:, off:off + w]
        words = tile.view(np.uint32).reshape(k, -1, LANES)
        pending.append((off, w, _matmul_tile(G8, words, interpret=interpret)))
    out = np.empty((m, L), np.uint8)
    for off, w, res in pending:
        out[:, off:off + w] = \
            np.asarray(res).reshape(m, -1).view(np.uint8)[:, :w]
    return out


def warmup_bitsliced(m: int, k: int, *, interpret: bool) -> None:
    """Compile (and run once) the tile program for every bucket of an
    (m, k) geometry, so no later call compiles."""
    G = np.zeros((m, k), np.uint8)
    for width in TILE_BUCKETS:
        words = np.zeros((k, width // _ROW_BYTES, LANES), np.uint32)
        _matmul_tile(G, words, interpret=interpret).block_until_ready()

