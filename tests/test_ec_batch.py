"""Batched EC data path: encode_many/decode_many round-trips over every
survivor subset, decode-matrix LRU accounting, bit-sliced kernel
equivalence, and store-level put_many/get_many."""
from itertools import combinations

import numpy as np
import pytest

from repro.core.ec import ECConfig, RSCodec
from repro.kernels.rs_gf256.kernel import gf256_matmul_bitsliced
from repro.kernels.rs_gf256.ref import (gf256_matmul_ref, gf_matmul_np,
                                        gf_matmul_table)


# ---------------------------------------------------------------------------
# codec: batched round-trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,p", [(2, 1), (3, 2), (4, 2)])
def test_roundtrip_all_survivor_subsets(k, p):
    rng = np.random.default_rng(k * 10 + p)
    codec = RSCodec(ECConfig(k=k, p=p))
    for size in (0, 1, 3, 100, 4097):
        payload = rng.bytes(size)
        chunks = codec.encode(payload)
        assert len(chunks) == k + p
        for surv in combinations(range(k + p), k):
            got = codec.decode({i: chunks[i] for i in surv})
            assert got == payload, (k, p, size, surv)


def test_encode_many_matches_encode():
    rng = np.random.default_rng(7)
    codec = RSCodec(ECConfig(k=4, p=2))
    payloads = [rng.bytes(s) for s in (10, 999, 0, 4096, 1, 123_457)]
    batched = codec.encode_many(payloads)
    for payload, chunks in zip(payloads, batched):
        assert chunks == codec.encode(payload)


def test_decode_many_mixed_survivor_sets():
    rng = np.random.default_rng(8)
    codec = RSCodec(ECConfig(k=4, p=2))
    payloads = [rng.bytes(s) for s in (50, 2048, 7, 0)]
    batched = codec.encode_many(payloads)
    cmaps, want = [], []
    for payload, chunks in zip(payloads, batched):
        for drop in ((), (0,), (1, 5), (2, 3), (4, 5)):
            cmaps.append({i: c for i, c in enumerate(chunks)
                          if i not in drop})
            want.append(payload)
    assert codec.decode_many(cmaps) == want


def test_decode_many_empty_and_too_few():
    codec = RSCodec(ECConfig(k=4, p=2))
    assert codec.decode_many([]) == []
    chunks = codec.encode(b"hello")
    with pytest.raises(ValueError):
        codec.decode_many([{0: chunks[0], 1: chunks[1], 2: chunks[2]}])


# ---------------------------------------------------------------------------
# codec: decode-matrix LRU cache accounting
# ---------------------------------------------------------------------------

def test_repeated_degraded_reads_invert_once():
    codec = RSCodec(ECConfig(k=4, p=2))
    chunks = codec.encode(b"x" * 5000)
    surv = {i: c for i, c in enumerate(chunks) if i not in (0, 5)}
    for _ in range(6):
        assert codec.decode(surv) == b"x" * 5000
    info = codec.cache_info()
    assert info["inversions"] == 1
    assert info["misses"] == 1
    assert info["hits"] == 5


def test_cache_keys_by_survivor_tuple_and_evicts_lru():
    codec = RSCodec(ECConfig(k=3, p=2), inv_cache_size=2)
    chunks = codec.encode(bytes(range(100)))
    survivor_sets = [(0, 1, 3), (0, 1, 4), (0, 2, 3)]   # 3 distinct keys
    for surv in survivor_sets:
        codec.decode({i: chunks[i] for i in surv})
    assert codec.cache_info()["inversions"] == 3
    assert codec.cache_info()["size"] == 2              # LRU evicted one
    # oldest key (0,1,3) was evicted -> re-decoding re-inverts
    codec.decode({i: chunks[i] for i in survivor_sets[0]})
    assert codec.cache_info()["inversions"] == 4


def test_identity_decode_skips_matmul_and_cache():
    codec = RSCodec(ECConfig(k=4, p=2))
    chunks = codec.encode(b"abcdef" * 100)
    codec.decode({i: chunks[i] for i in range(4)})       # all data rows
    info = codec.cache_info()
    assert info["inversions"] == 0 and info["hits"] == 0


# ---------------------------------------------------------------------------
# kernel: bit-sliced vs oracles (bit-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_bitsliced_bit_identical_randomized(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 8))
    k = int(rng.integers(1, 13))
    L = int(rng.integers(1, 9000))
    G = rng.integers(0, 256, (m, k)).astype(np.uint8)
    X = rng.integers(0, 256, (k, L)).astype(np.uint8)
    want = gf_matmul_np(G, X)
    assert np.array_equal(np.asarray(gf256_matmul_ref(G, X)), want)
    assert np.array_equal(gf_matmul_table(G, X), want)
    got = np.asarray(gf256_matmul_bitsliced(G, X, interpret=True))
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# store: batch APIs
# ---------------------------------------------------------------------------

def test_put_many_get_many_roundtrip(tiny_store):
    store, clock = tiny_store
    rng = np.random.default_rng(3)
    items = {f"k{i}": rng.bytes(int(rng.integers(1, 200_000)))
             for i in range(5)}
    vers = store.put_many(items)
    assert all(v >= 1 for v in vers.values())
    got = store.get_many(list(items) + ["absent"])
    for key, want in items.items():
        assert got[key] == want
    assert got["absent"] is None


def test_put_many_replaces_chunks_refused_by_drifted_slabs():
    """Regression: batch placement runs before any slab write, so the
    ledger/slab drift resync of the sequential path can't trigger at
    place time — a refused chunk must be re-placed, not fail the PUT."""
    from repro.core import Clock, InfiniStore, StoreConfig
    from repro.core.ec import ECConfig
    MB = 1024 * 1024
    store = InfiniStore(StoreConfig(ec=ECConfig(k=2, p=1),
                                    function_capacity=2 * MB,
                                    fragment_bytes=1 * MB), clock=Clock())
    rng = np.random.default_rng(5)
    for i in range(30):                 # builds ledger-vs-slab drift
        store.put(f"k{i % 7}", rng.bytes(int(rng.integers(1, 300_000))))
    big = rng.bytes(2_500_000)
    out = store.put_many([("big1", big), ("tiny", b"t")])
    assert out == {"big1": 1, "tiny": 1}
    assert store.get("big1") == big
    assert store.get("tiny") == b"t"


def test_put_many_rejects_duplicate_keys(tiny_store):
    store, _ = tiny_store
    with pytest.raises(ValueError):
        store.put_many([("k", b"a"), ("k", b"b")])


def test_store_configs_are_not_shared():
    """Regression: the cfg default must be per-instance, not a shared
    dataclass default evaluated once at def time."""
    from repro.core import InfiniStore
    s1, s2 = InfiniStore(), InfiniStore()
    assert s1.cfg is not s2.cfg
    s1.cfg.fragment_bytes = 1
    assert s2.cfg.fragment_bytes != 1
