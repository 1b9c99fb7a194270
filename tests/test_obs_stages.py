"""Stage spans of the PUT path (`repro.obs.sites.STAGE_SITES`): a traced
`put_many` through a store with a spill journal and the RS kernel (in
the Pallas interpreter) names the queue wait, the codec's framing,
dispatch and collection, the SMS placement and store, and the journal's
lock wait, digest and writes; span records carry their start, CPU time
and thread; the byte tags add up to what was sent and written; stage
spans are leaves and mirror into the profiler; a store without a plane
records nothing and opens no profiler annotation."""
import time

import jax
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.core import Clock, InfiniStore, StoreConfig
from repro.core.ec import ECConfig, RSCodec
from repro.core.gc_window import GCConfig
from repro.core.spill import SpillJournal
from repro.devtools import lint
from repro.kernels.rs_gf256.kernel import column_tiles
from repro.obs import SPAN_SITES, STAGE_SITES, ObsPlane, summarize

MB = 1024 * 1024
PAYLOADS = (3_000, 5_000, 9_000)


def _store(tmp_path, plane, *, interpret=True):
    cfg = StoreConfig(ec=ECConfig(k=4, p=2), function_capacity=8 * MB,
                      fragment_bytes=1 * MB, gc=GCConfig(gc_interval=1e9),
                      num_recovery_functions=4,
                      spill_dir=str(tmp_path / "spill"), obs=plane)
    st = InfiniStore(cfg, clock=Clock(), seed=0)
    if interpret:
        st.codec = RSCodec(cfg.ec, backend="interpret", obs=plane)
    return st


def _items(seed=0):
    rng = np.random.default_rng(seed)
    return {f"k{i}": rng.bytes(n) for i, n in enumerate(PAYLOADS)}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans of one traced put_many, with the writeback drained."""
    tmp = tmp_path_factory.mktemp("traced")
    plane = ObsPlane(name="stages")
    st = _store(tmp, plane)
    try:
        st.put_many(_items())
        st.flush_writeback()
        spans = st.snapshot_metrics()["spans"]
        clens = [st.codec.chunk_len(n) for n in PAYLOADS]
        k = st.cfg.ec.k
    finally:
        st.close()
    return spans, clens, k


def test_put_many_names_every_stage(traced):
    spans, _, _ = traced
    sites = {s["site"] for s in spans}
    assert {"daemon.queue", "ec.frame", "ec.dispatch", "ec.collect",
            "sms.place", "sms.store", "journal.lock_wait",
            "journal.digest", "journal.write"} <= sites
    for s in spans:
        assert isinstance(s["t0_us"], float) and s["dur_us"] >= 0
        assert isinstance(s["tid"], int)
        if s["site"] in ("daemon.queue", "journal.lock_wait"):
            assert s["cpu_us"] is None        # recorded after the wait
        else:
            assert s["cpu_us"] >= 0
    put = next(s for s in spans if s["site"] == "daemon.put_many")
    frame = next(s for s in spans if s["site"] == "ec.frame")
    # one perf_counter clock: the stage lies inside its operation
    assert put["t0_us"] <= frame["t0_us"]
    assert frame["t0_us"] + frame["dur_us"] <= put["t0_us"] + put["dur_us"]


def test_stage_spans_are_leaves_and_the_queue_parents_under_the_client(
        traced):
    spans, _, _ = traced
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        parent = by_id.get(s["parent_id"])
        assert parent is None or parent["site"] not in STAGE_SITES, \
            (parent["site"], s["site"])
    queue = [s for s in spans if s["site"] == "daemon.queue"]
    assert [by_id[s["parent_id"]]["site"] for s in queue] == \
        ["client.put_many"]
    assert queue[0]["tags"] == {"op": "put", "n": len(PAYLOADS)}


def test_dispatch_bytes_are_the_padded_tiles(traced):
    spans, clens, k = traced
    sent = sum(s["tags"]["bytes"] for s in spans
               if s["site"] == "ec.dispatch")
    assert sent == k * sum(b for _, _, b in column_tiles(sum(clens)))
    back = sum(s["tags"]["bytes"] for s in spans
               if s["site"] == "ec.collect")
    assert back == 2 * sent // k                # p = 2 parity rows
    framed = sum(s["tags"]["bytes"] for s in spans
                 if s["site"] == "ec.frame")
    assert framed == k * sum(clens)


def test_journal_write_bytes_are_the_frames_on_disk(tmp_path):
    """Σ `journal.write` bytes equals the bytes of every frame the journal
    wrote (header, key and payload; PERSIST frames included)."""
    plane = ObsPlane(name="journal")
    j = SpillJournal(tmp_path / "j", sync_each=False)
    j.obs = plane
    payloads = [np.full(n, i, np.uint8) for i, n in
                enumerate((10, 4_000, 70_000, 0))]
    seqs = j.append_many([(f"frag/{i}", p) for i, p in enumerate(payloads)])
    j.append("meta/x", b"{}")
    j.mark_persisted(seqs[0])                 # one PERSIST frame
    j.sync()
    spans = plane.snapshot()["spans"]
    wrote = sum(s["tags"]["bytes"] for s in spans
                if s["site"] == "journal.write")
    on_disk = sum(p.stat().st_size for p in (tmp_path / "j").glob("*.wal"))
    assert wrote == on_disk > sum(p.size for p in payloads)
    # payload-less frames (one stub, one PERSIST) have no span of their
    # own: they count on the flush at sync
    writes = [s for s in spans if s["site"] == "journal.write"]
    assert len(writes) == 4 + 1
    digests = [s["tags"]["bytes"] for s in spans
               if s["site"] == "journal.digest"]
    # every frame with a payload is digested; empty ones have no span
    assert sorted(digests) == sorted([p.size for p in payloads if p.size]
                                     + [2])
    assert sum(s["site"] == "journal.lock_wait" for s in spans) == 3
    j.close()


def test_compaction_and_rotation_are_stages(tmp_path):
    plane = ObsPlane(name="segments")
    j = SpillJournal(tmp_path / "j", segment_bytes=4_096,
                     compact_below=1_024)
    j.obs = plane
    keep = j.append("meta/keep", b"m" * 50)
    big = j.append("frag/big", np.zeros(5_000, np.uint8))   # rotates
    j.mark_persisted(big)     # the sealed segment keeps 50 live bytes
    j.sync()
    spans = plane.snapshot()["spans"]
    ops = {s["tags"]["op"] for s in spans if s["site"] == "journal.segment"}
    assert "rotate" in ops
    moved = [s["tags"]["bytes"] for s in spans
             if s["site"] == "journal.compact"]
    assert moved and moved[0] == 29 + len("meta/keep") + 50
    assert keep in j._records
    j.close()


class _Front:
    """A pass-through frontend, as the benchmark wraps the store."""

    def __init__(self, store):
        self._store = store

    def __getattr__(self, name):
        return getattr(self._store, name)


def test_traced_checkpoint_save_names_its_stages(tmp_path):
    plane = ObsPlane(name="ckpt")
    st = _store(tmp_path, plane, interpret=False)
    try:
        state = {"w": jax.numpy.arange(3_000, dtype=jax.numpy.float32),
                 "b": np.ones(500, np.float32)}
        Checkpointer(_Front(st)).save(1, state)
        spans = st.snapshot_metrics()["spans"]
    finally:
        st.close()
    root = [s for s in spans if s["site"] == "ckpt.save"]
    assert len(root) == 1 and root[0]["tags"]["bytes"] == 14_000
    under = [s for s in spans if s["parent_id"] == root[0]["span_id"]]
    d2h = [s for s in under if s["site"] == "ckpt.d2h"]
    assert sorted(s["tags"]["bytes"] for s in d2h) == [2_000, 12_000]
    assert any(s["site"] == "ckpt.wait" for s in under)
    # the save's PUTs stitch into its trace, across the daemon hop
    assert any(s["site"] == "daemon.put_many"
               and s["trace_id"] == root[0]["trace_id"] for s in spans)


def test_stage_sites_mirror_into_the_profiler(tmp_path, monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    plane = ObsPlane(name="mirror")
    st = _store(tmp_path, plane)
    try:
        st.put_many(_items(1))
    finally:
        st.close()
    assert {"ec.frame", "ec.dispatch", "ec.collect", "sms.place",
            "journal.digest", "journal.write"} <= set(opened)
    assert set(opened) <= STAGE_SITES        # no outer span is mirrored


def test_no_plane_records_nothing_and_annotates_nothing(tmp_path,
                                                        monkeypatch):
    def refuse(name):
        raise AssertionError(f"profiler annotation {name!r} without a "
                             "plane")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    st = _store(tmp_path, None)
    try:
        assert st.obs is None and st.codec.obs is None
        assert st.spill.obs is None
        st.put_many(_items(2))
        Checkpointer(st).save(1, {"w": np.arange(100, dtype=np.int32)})
        assert st.get("k0") == _items(2)["k0"]
        snap = st.snapshot_metrics()
        assert snap["enabled"] is False and not snap.get("spans")
    finally:
        st.close()


def test_ack_latency_counts_the_queue_wait(tmp_path):
    """`put.ack_us` runs from the submit, so a PUT queued behind a slow
    task on the daemon carries that wait."""
    plane = ObsPlane(name="ack")
    st = _store(tmp_path, plane, interpret=False)
    try:
        blocker = st._submit(lambda: time.sleep(0.2))
        fut = st.put_async("late", b"x" * 2_000)
        blocker.result()
        assert fut.result() > 0
        snap = st.snapshot_metrics()
        ack = summarize(snap["histograms"]["put.ack_us"]["buckets"])
        assert ack["count"] == 1 and ack["p50_us"] >= 150_000
        queue = [s for s in snap["spans"] if s["site"] == "daemon.queue"
                 and s["tags"]["op"] == "put"]
        assert len(queue) == 1 and queue[0]["dur_us"] >= 150_000
    finally:
        st.close()


def test_stage_sites_are_registered_spans():
    assert STAGE_SITES <= SPAN_SITES
    assert "daemon.put_many" not in STAGE_SITES
    assert "sms.store" not in STAGE_SITES     # the insertion log journals


def test_metric_site_rule_polices_span_since(tmp_path):
    (tmp_path / "sites.py").write_text(
        'METRIC_SITES = frozenset({"ok.wait"})\n')
    (tmp_path / "m.py").write_text(
        "class C:\n"
        "    obs = None\n\n"
        "    def waited(self, t0):\n"
        "        obs = self.obs\n"
        "        if obs is not None:\n"
        "            obs.span_since('ok.wait', t0)\n"
        "            obs.span_since('typo.wait', t0)\n")
    new, _ = lint.run([str(tmp_path)], root=tmp_path,
                      baseline_path=tmp_path / "absent.json")
    assert [f.detail for f in new if f.rule == "metric-site"] == \
        ["unregistered:typo.wait"]


def test_flight_file_keeps_operation_spans_in_compact_records(tmp_path):
    """Stage, per-record and per-chunk spans stay out of the flight file
    (tens per PUT would overwrite its ring); a mirrored operation span
    fits a slot whole."""
    from repro.obs import FlightRecorder
    plane = ObsPlane(name="flight")
    path = str(tmp_path / "flight.bin")
    assert plane.bind_flight(path)
    with plane.span("daemon.put_many", n=1):
        with plane.span("journal.append"):
            with plane.span("journal.write", bytes=10):
                pass
        with plane.span("wb.persist"):
            pass
    plane.close()
    recs = [r for r in FlightRecorder.read_file(path)
            if r.get("kind") == "span"]
    assert [r["site"] for r in recs] == ["daemon.put_many"]
    assert "truncated" not in recs[0] and "t0_us" not in recs[0]
