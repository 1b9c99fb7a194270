"""The spill journal's payload digest (`spill._payload_sig`), computed in
fixed blocks of `_SIG_BLOCK` words, against the one-pass definition kept
here as the reference: equal bytes for every size, offset and buffer
type; a frame format that replays both ways between the two; memory
that does not grow with the payload; and the `staged` tag of the
`journal.digest` span."""
import struct
import threading
import tracemalloc
import warnings
import zlib

import numpy as np
import pytest

from repro.core import spill
from repro.core.spill import SpillJournal
from repro.obs import ObsPlane

B8 = 8 * spill._SIG_BLOCK                   # bytes in one digest block
SIZES = (0, 1, 7, 8, 9, B8 - 1, B8, B8 + 1, 3 * B8 + 5, 9_900_001)


def reference_sig(payload) -> bytes:
    """The digest's definition in one pass over the whole payload: u64
    word sum (+ length), sum of word i times 2i+1, word xor, each mod
    2^64, then the < 8 trailing bytes verbatim."""
    u8 = np.frombuffer(bytes(memoryview(payload).cast("B")), np.uint8)
    n = u8.size
    if n == 0:
        return b""
    m = n & ~7
    h_sum = h_pos = h_xor = 0
    if m:
        u64 = u8[:m].view(np.uint64)
        w = (np.arange(u64.size, dtype=np.uint64) << np.uint64(1)) \
            + np.uint64(1)
        h_sum = int(u64.sum(dtype=np.uint64))
        with np.errstate(over="ignore"):
            h_pos = int(np.dot(u64, w))
        h_xor = int(np.bitwise_xor.reduce(u64, dtype=np.uint64))
    return struct.pack("<QQQ", (h_sum + n) & 0xFFFFFFFFFFFFFFFF,
                       h_pos & 0xFFFFFFFFFFFFFFFF, h_xor) + u8[m:].tobytes()


@pytest.fixture(scope="module")
def pool():
    return np.random.default_rng(15).integers(
        0, 256, max(SIZES) + 8, dtype=np.uint8)


def _as(kind: str, buf: np.ndarray):
    if kind == "bytes":
        return buf.tobytes()
    if kind == "memoryview":
        return memoryview(buf)
    if kind == "strided ndarray":       # every other byte of a 2n buffer
        wide = np.zeros(2 * buf.size, np.uint8)
        wide[::2] = buf
        return wide[::2]
    view = buf.view()
    view.flags.writeable = False
    return view


@pytest.mark.parametrize("kind", ["bytes", "memoryview",
                                  "read-only ndarray", "strided ndarray"])
@pytest.mark.parametrize("offset", range(8))
@pytest.mark.parametrize("size", SIZES)
def test_blockwise_digest_equals_reference(pool, size, offset, kind):
    payload = _as(kind, pool[offset:offset + size])
    got = spill._payload_sig(payload)
    assert got == reference_sig(pool[offset:offset + size])
    assert len(got) == (0 if size == 0 else 24 + size % 8)


def test_word_swap_across_a_block_boundary_changes_the_digest(pool):
    words = pool[:2 * B8].copy().view(np.uint64)
    b = spill._SIG_BLOCK
    assert words[b - 1] != words[b]
    swapped = words.copy()
    swapped[[b - 1, b]] = words[[b, b - 1]]
    # the word sum and xor cannot see a swap; the weighted sum must
    assert spill._payload_sig(swapped.view(np.uint8)) != \
        spill._payload_sig(words.view(np.uint8))
    assert spill._payload_sig(swapped.view(np.uint8)) == \
        reference_sig(swapped.view(np.uint8))


def _frame(rtype: int, seq: int, key: bytes, payload: bytes) -> bytes:
    meta = spill._META_S.pack(rtype, seq, len(key), len(payload))
    crc = zlib.crc32(reference_sig(payload),
                     zlib.crc32(key, zlib.crc32(meta))) & 0xFFFFFFFF
    return (spill._MAGIC_S.pack(spill._MAGIC) + meta
            + spill._CRC_S.pack(crc) + key + payload)


def test_segment_with_reference_digests_replays_whole(tmp_path, pool):
    """Frames whose CRCs carry the reference digest (as an older writer
    wrote them) replay under the blockwise digest with none torn."""
    records = [(f"frag/{i}", pool[i:i + n].tobytes()) for i, n in
               enumerate((0, 5, 8, B8 - 3, B8 + 11, 3 * B8 + 5))]
    with open(tmp_path / "seg-00000001.wal", "wb") as f:
        for seq, (key, payload) in enumerate(records, 1):
            f.write(_frame(spill._APPEND, seq, key.encode(), payload))
    j = SpillJournal(tmp_path)
    try:
        assert j.stats.torn_records == 0
        assert [(k, bytes(p)) for _, k, p in j.take_pending()] == records
    finally:
        j.close(reclaim=False)


def test_journal_frames_verify_against_the_reference(tmp_path, pool):
    """The other way round: frames the journal writes from unaligned
    views carry CRCs the reference digest reproduces."""
    j = SpillJournal(tmp_path)
    payloads = [pool[3:3 + n] for n in (9, B8 + 1, 2 * B8 + 13)]
    for i, p in enumerate(payloads):
        j.append(f"frag/{i}", p)
    j.close(reclaim=False)
    (seg,) = sorted(tmp_path.glob("*.wal"))
    data, off = seg.read_bytes(), 0
    for p in payloads:
        meta = data[off + 4:off + 4 + spill._META_S.size]
        _, _, klen, plen = spill._META_S.unpack(meta)
        (crc,) = spill._CRC_S.unpack_from(data, off + 4 + len(meta))
        key = data[off + spill._HDR_LEN:off + spill._HDR_LEN + klen]
        body = data[off + spill._HDR_LEN + klen:
                    off + spill._HDR_LEN + klen + plen]
        assert body == p.tobytes()
        assert crc == zlib.crc32(reference_sig(body), zlib.crc32(
            key, zlib.crc32(meta))) & 0xFFFFFFFF
        off += spill._HDR_LEN + klen + plen
    assert off == len(data)


def test_digest_memory_does_not_grow_with_the_payload():
    """A 64 MiB unaligned payload is digested through one staging block:
    no weight vector or copy as large as the payload."""
    buf = np.ones(64 * 1024 * 1024 + 8, np.uint8)
    payload = buf[3:3 + 64 * 1024 * 1024]
    peak = []

    def digest():                   # a fresh thread: its block is new too
        tracemalloc.start()
        try:
            spill._payload_sig(payload)
            peak.append(tracemalloc.get_traced_memory()[1])
        finally:
            tracemalloc.stop()

    t = threading.Thread(target=digest)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive() and peak
    assert peak[0] < 4 * B8


def test_digest_span_tags_the_staged_bytes(tmp_path):
    """`journal.digest` tags `staged`: the bytes copied through the
    aligned block, i.e. an unaligned payload's 8-aligned prefix."""
    plane = ObsPlane(name="digest")
    j = SpillJournal(tmp_path / "j")
    j.obs = plane
    buf = np.arange(B8 + 64, dtype=np.uint64).view(np.uint8)
    j.append("aligned", buf[:B8 + 5])
    j.append("unaligned", buf[3:3 + B8 + 5])
    j.append("short", buf[1:6])
    j.close()
    spans = [s for s in plane.snapshot()["spans"]
             if s["site"] == "journal.digest"]
    assert [(s["tags"]["bytes"], s["tags"]["staged"]) for s in spans] == \
        [(B8 + 5, 0), (B8 + 5, B8), (5, 0)]


def test_digest_wraps_mod_2_64_without_warnings():
    payload = np.full(2 * B8 + 11, 0xFF, np.uint8)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = spill._payload_sig(payload[1:])
    assert got == reference_sig(payload[1:])
