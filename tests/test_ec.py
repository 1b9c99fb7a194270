"""Reed-Solomon codec: roundtrip under any <= p erasures (property)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.ec import ECConfig, RSCodec


@settings(max_examples=40, deadline=None)
@given(
    k=st.integers(2, 10),
    p=st.integers(1, 4),
    size=st.integers(0, 5000),
    seed=st.integers(0, 2**31 - 1),
)
def test_roundtrip_with_erasures(k, p, size, seed):
    rng = np.random.default_rng(seed)
    codec = RSCodec(ECConfig(k=k, p=p))
    payload = rng.integers(0, 256, size).astype(np.uint8).tobytes()
    chunks = codec.encode(payload)
    assert len(chunks) == k + p
    assert len({len(c) for c in chunks}) == 1        # equal-size chunks
    lost = rng.choice(k + p, size=rng.integers(0, p + 1), replace=False)
    surviving = {i: c for i, c in enumerate(chunks) if i not in lost}
    assert codec.decode(surviving) == payload


def test_too_few_chunks_raises():
    codec = RSCodec(ECConfig(k=4, p=2))
    chunks = codec.encode(b"hello world")
    with pytest.raises(ValueError):
        codec.decode({0: chunks[0], 1: chunks[1], 2: chunks[2]})


def test_parity_only_decode():
    """All data chunks lost, k survivors include all parity."""
    codec = RSCodec(ECConfig(k=3, p=2))
    payload = bytes(range(256)) * 7
    chunks = codec.encode(payload)
    surviving = {0: chunks[0], 3: chunks[3], 4: chunks[4]}
    assert codec.decode(surviving) == payload


def test_paper_config_10_2():
    codec = RSCodec(ECConfig(k=10, p=2))
    payload = np.random.default_rng(1).integers(
        0, 256, 1_000_000).astype(np.uint8).tobytes()
    chunks = codec.encode(payload)
    surviving = {i: c for i, c in enumerate(chunks) if i not in (2, 11)}
    assert codec.decode(surviving) == payload


def test_pallas_backend_matches_numpy():
    """The bit-sliced kernel (here in the Pallas interpreter) is
    bit-identical to the numpy table, encode and decode."""
    payload = np.random.default_rng(2).integers(
        0, 256, 10000).astype(np.uint8).tobytes()
    c_np = RSCodec(ECConfig(k=4, p=2), backend="numpy")
    c_pl = RSCodec(ECConfig(k=4, p=2), backend="interpret")
    assert c_np.encode(payload) == c_pl.encode(payload)
    chunks = dict(enumerate(c_np.encode(payload)))
    del chunks[1], chunks[4]
    assert c_pl.decode(chunks) == payload


def test_pallas_backend_requires_tpu():
    """RSCodec(backend="pallas") demands the compiled kernel; off a TPU
    it raises, while the default picks the host table."""
    with pytest.raises(RuntimeError, match="TPU"):
        RSCodec(ECConfig(k=4, p=2), backend="pallas")
    assert RSCodec(ECConfig(k=4, p=2)).backend == "numpy"


def test_codec_compiles_one_program_per_bucket():
    """However payload lengths mix, the codec's kernel builds one program
    per (geometry, width bucket), never one per length: once each bucket
    has been seen, a spread of new lengths compiles nothing."""
    from repro.kernels.rs_gf256.kernel import (TILE_BUCKETS, _matmul_tile,
                                               column_tiles)
    rng = np.random.default_rng(5)
    codec = RSCodec(ECConfig(k=4, p=2), backend="interpret")
    buckets = TILE_BUCKETS[:5]

    def roundtrip(n):
        payload = rng.bytes(n)
        chunks = dict(enumerate(codec.encode(payload)))
        del chunks[0], chunks[3]                  # decode needs parity
        assert codec.decode(chunks) == payload

    before = _matmul_tile._cache_size()
    for b in buckets:                 # chunk_len == b: one of each bucket
        roundtrip(4 * b - 4)
    warm = _matmul_tile._cache_size()
    assert warm - before <= 2 * len(buckets)   # encode (2,4), decode (4,4)
    lengths = sorted({int(n) for n in rng.integers(1, 4 * buckets[-1] - 4,
                                                   20)})
    for n in lengths:
        roundtrip(n)
    assert {b for n in lengths
            for _, _, b in column_tiles(codec.chunk_len(n))} <= set(buckets)
    assert _matmul_tile._cache_size() == warm
