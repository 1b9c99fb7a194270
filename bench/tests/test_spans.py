"""Span arithmetic (`bench/harness/spans.py`) and the per-layer readers
built on it, on hand-built span lists: nested and overlapping children
for self time, children on another thread, tag sums, and records as an
older program writes them (no start, thread or CPU time)."""
import types

import pytest

from bench.harness import spans, spec, stats

GiB = 1 << 30


def _span(site, sid, parent, t0, dur, *, tid=1, cpu=None, pid=7, **tags):
    return {"site": site, "span_id": sid, "parent_id": parent,
            "trace_id": "t", "t0_us": t0, "dur_us": dur,
            "cpu_us": cpu, "tid": tid, "pid": pid, "epoch": None,
            "tags": tags}


# one daemon.put_many of 1000 µs on thread 1: children [100, 300) and
# [250, 400) overlap, [500, 600) holds a grandchild, [900, 1100) runs
# past the parent's end; a writeback span on thread 2 covers nothing
PUT = [
    _span("daemon.put_many", "p", None, 0.0, 1000.0, cpu=600.0),
    _span("ec.encode", "a", "p", 100.0, 200.0, cpu=200.0),
    _span("journal.append", "b", "p", 250.0, 150.0),
    _span("sms.store", "c", "p", 500.0, 100.0),
    _span("journal.append", "d", "c", 520.0, 50.0),
    _span("journal.sync", "e", "p", 900.0, 200.0),
    _span("wb.persist", "w", "c", 0.0, 1000.0, tid=2),
]


def test_union_length_merges_overlaps_and_nesting():
    assert spans.union_length([]) == 0.0
    assert spans.union_length([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert spans.union_length([(5, 5), (3, 2)]) == 0.0
    assert spans.union_length([(0, 10), (10, 12)]) == 12


def test_self_time_takes_the_union_of_same_thread_children():
    # covered: [100, 400) + [500, 600) + [900, 1000) = 500 µs
    assert spans.self_seconds(PUT, "daemon.put_many") == \
        pytest.approx(500e-6)
    # sms.store: its journal child covers 50 µs, the writeback none
    assert spans.self_seconds(PUT, "sms.store") == pytest.approx(50e-6)
    assert spans.self_seconds(PUT, "journal.append") == \
        pytest.approx(200e-6)
    assert spans.self_seconds(PUT, "ec.frame") is None


def test_sums_shares_and_tags():
    assert spans.seconds(spans.of(PUT, "journal.append")) == \
        pytest.approx(200e-6)
    assert spans.seconds([]) is None
    assert spans.cpu_share(PUT, "daemon.put_many") == pytest.approx(60.0)
    assert spans.cpu_share(PUT, "journal.append") is None   # no CPU time
    tagged = [_span("ec.dispatch", "x", None, 0.0, 1.0, bytes=40),
              _span("ec.dispatch", "y", None, 2.0, 1.0, bytes=2),
              _span("ec.dispatch", "z", None, 4.0, 1.0)]
    assert spans.tag_sum(tagged, "bytes") == 42
    assert spans.tag_sum(tagged, "n") is None
    assert spans.per_gib(2.0, GiB // 2) == 4.0
    assert spans.per_gib(None, GiB) is None and spans.per_gib(1.0, 0) is None


def _run(span_list, put_bytes=GiB):
    return types.SimpleNamespace(spans=span_list, put_bytes=put_bytes,
                                 window_s=10.0)


def _read(metric, run):
    return spec.load_reader(metric)(run)


def test_readers_on_a_hand_built_put():
    S = _span
    run = _run(PUT + [
        S("daemon.queue", "q1", None, 0.0, 4000.0, op="put", n=1),
        S("daemon.queue", "q2", None, 0.0, 1000.0, op="put", n=1),
        S("daemon.queue", "q3", None, 0.0, 9000.0, op="get", n=1),
        S("ec.frame", "f", "a", 100.0, 50.0, cpu=50.0, bytes=100),
        S("ec.dispatch", "h", "a", 150.0, 100.0, cpu=90.0, bytes=1 << 30),
        S("ec.collect", "o", "a", 250.0, 40.0, cpu=10.0, bytes=1 << 28),
        S("sms.place", "pl", "p", 420.0, 30.0, cpu=30.0, bytes=5),
        S("journal.lock_wait", "l", "b", 250.0, 20.0),
        S("journal.digest", "g", "b", 270.0, 60.0, cpu=60.0, bytes=9),
        S("journal.write", "r", "b", 330.0, 70.0, cpu=10.0,
          bytes=3 << 29),
        S("journal.compact", "k", None, 700.0, 10.0, cpu=10.0,
          bytes=1 << 29),
        S("ckpt.d2h", "c1", None, 0.0, 300.0, cpu=20.0, bytes=64),
        S("ckpt.wait", "c2", None, 300.0, 800.0, cpu=1.0),
    ])
    want = {
        "put.queue_p95_ms": stats.percentile([4.0, 1.0], 95),
        "put.daemon_self_s_per_GiB": 470e-6,     # sms.place covers 30 µs
        "put.daemon_cpu_share": 60.0,
        "sms.store_s_per_GiB": 100e-6,
        "sms.place_s_per_GiB": 30e-6,
        "codec.frame_s_per_GiB": 50e-6,
        "codec.dispatch_s_per_GiB": 100e-6,
        "codec.collect_s_per_GiB": 40e-6,
        "codec.h2d_bytes_per_byte": 1.0,
        "journal.lock_wait_s_per_GiB": 20e-6,
        "journal.digest_s_per_GiB": 60e-6,
        "journal.write_s_per_GiB": 70e-6,
        "journal.bytes_per_byte": 2.0,
        "ckpt.d2h_s_per_GiB": 300e-6,
        "ckpt.wait_s_per_GiB": 800e-6,
    }
    for metric, value in want.items():
        assert _read(metric, run) == pytest.approx(value), metric
    # per GiB of PUT payload acked: half the bytes, twice the seconds
    half = _run(run.spans, GiB // 2)
    assert _read("codec.frame_s_per_GiB", half) == pytest.approx(100e-6)
    assert _read("codec.h2d_bytes_per_byte", half) == pytest.approx(2.0)


NEW = ("put.queue_p95_ms", "put.daemon_self_s_per_GiB",
       "put.daemon_cpu_share", "sms.store_s_per_GiB",
       "sms.place_s_per_GiB", "codec.frame_s_per_GiB",
       "codec.dispatch_s_per_GiB", "codec.collect_s_per_GiB",
       "codec.h2d_bytes_per_byte", "journal.lock_wait_s_per_GiB",
       "journal.digest_s_per_GiB", "journal.write_s_per_GiB",
       "journal.bytes_per_byte", "ckpt.d2h_s_per_GiB",
       "ckpt.wait_s_per_GiB")


@pytest.mark.parametrize("metric", NEW)
def test_reader_is_silent_on_an_older_program(metric):
    """Spans as a program without the stage sites writes them: the
    readers give None and raise nothing."""
    old = [{k: v for k, v in s.items() if k not in ("t0_us", "cpu_us",
                                                    "tid")}
           for s in PUT if s["site"] != "sms.store"]
    assert _read(metric, _run(old)) is None
    assert _read(metric, _run([])) is None
    if "per_" in metric:                  # nothing acked: no base
        assert _read(metric, _run(PUT, put_bytes=0)) is None


def test_new_readers_are_in_the_benchmark():
    names = {m["name"] for m in spec.load_benchmark()["per_layer"]}
    assert set(NEW) <= names
