"""Multi-process shard host (`repro.core.host` + `repro.core.ipc`):
shared-memory ring mechanics, worker lifecycle hygiene (no stray
processes or /dev/shm segments), and REAL-SIGKILL durability — the
cross-process version of test_shard_2pc: kill a worker with a prepared
2PC ticket outstanding, survivors keep serving, restart replays the
journal, and the sweep leaves zero PENDING keys."""
import gc
import os
import pickle
import signal
import threading
import time

import numpy as np
import pytest

from repro.core import (Clock, ConcurrentPutError, FaultPlan, FaultPoint,
                        InjectedCrash, ProcessShardedStore,
                        ShardWorkerDied, ShmArena, StoreConfig)
from repro.core.ec import ECConfig
from repro.core.gc_window import GCConfig
from repro.core.ipc import ArenaBroken, pack_payload, unpack_payload

MB = 1024 * 1024


def make_host(num_shards=2, *, spill_dir=None, cos_root=None,
              faults=None, seed=0, **kw):
    cfg = StoreConfig(ec=ECConfig(k=4, p=2),
                      function_capacity=8 * MB,
                      fragment_bytes=1 * MB,
                      gc=GCConfig(gc_interval=1e9),
                      num_recovery_functions=4,
                      spill_dir=spill_dir, faults=faults, **kw)
    return ProcessShardedStore(cfg, num_shards=num_shards, clock=Clock(),
                               cos_root=cos_root, seed=seed)


def cross_shard_batch(st, n_per_shard=2, tag="b", rng=None):
    rng = rng or np.random.default_rng(0)
    per = {sid: 0 for sid in range(st.num_shards)}
    out = {}
    i = 0
    while any(c < n_per_shard for c in per.values()):
        k = f"{tag}{i}"
        i += 1
        sid = st.router.shard_of(k)
        if per[sid] >= n_per_shard:
            continue
        per[sid] += 1
        out[k] = rng.bytes(12_000)
    return out


def _pids_gone(pids, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        alive = []
        for pid in pids:
            try:
                os.kill(pid, 0)
                alive.append(pid)
            except ProcessLookupError:
                pass
        if not alive:
            return True
        time.sleep(0.05)
    return False


def _shm_segments():
    try:
        return {n for n in os.listdir("/dev/shm")
                if n.startswith("infinistore-")}
    except FileNotFoundError:                # non-Linux: can't observe
        return set()


# ---------------------------------------------------------------------------
# ShmArena ring mechanics (no processes)
# ---------------------------------------------------------------------------

def test_arena_alloc_wraps_and_releases():
    a = ShmArena.create(1024, tag="t")
    try:
        positions = []
        for i in range(3):
            pos, view = a.alloc(400)
            view[:] = i
            del view                 # views must not outlive close()
            positions.append(pos)
            a.release_to(pos + 400)  # reader consumed immediately
        # two slots per revolution: the third alloc wrapped past the
        # physical end via padding, positions stay monotonic
        assert positions == sorted(positions)
        assert positions[2] % 1024 == 0     # padded to the wrap point
    finally:
        a.close()


def test_arena_blocks_until_release_then_fails_when_broken():
    a = ShmArena.create(1024, tag="t")
    try:
        pos, _ = a.alloc(1000)
        got = []

        def writer():
            try:
                got.append(a.alloc(1000, timeout=30.0)[0])
            except ArenaBroken as e:
                got.append(e)
        th = threading.Thread(target=writer)
        th.start()
        time.sleep(0.1)
        assert not got                       # full: writer is parked
        a.release_to(pos + 1000)
        th.join(timeout=10.0)
        assert got and isinstance(got[0], int)
        # a broken arena wakes + fails any parked writer
        th2 = threading.Thread(target=writer)
        th2.start()
        time.sleep(0.1)
        a.fail(ArenaBroken("peer died"))
        th2.join(timeout=10.0)
        assert isinstance(got[1], ArenaBroken)
    finally:
        a.close()


def test_payload_pack_zero_copy_and_inline_fallback():
    a = ShmArena.create(64 * 1024, tag="t")
    try:
        small = np.arange(100, dtype=np.uint8)
        d = pack_payload(a, small)
        assert d[0] == "a"
        view = unpack_payload(a, d)
        assert view.base is not None         # a VIEW into the ring
        assert np.array_equal(view, small)
        del view                             # must not outlive close()
        # oversized payloads fall back to inline bytes
        big = b"z" * (128 * 1024)
        d2 = pack_payload(a, big)
        assert d2[0] == "i" and unpack_payload(a, d2) == big
    finally:
        a.close()


def test_exceptions_cross_process_boundary():
    e = pickle.loads(pickle.dumps(ConcurrentPutError("kx")))
    assert isinstance(e, ConcurrentPutError) and e.key == "kx"
    from repro.core import TransientCOSError
    plan = FaultPlan(seed=7).add(
        FaultPoint(site="cos.put", action="transient", hits=(1,)))
    with pytest.raises(TransientCOSError):
        plan.fire("cos.put", "warm")         # hit 1 fires
    clone = pickle.loads(pickle.dumps(plan))
    assert clone.seed == 7
    # the log and hit counters resume from the serialized position —
    # each process then advances its own independent copy
    assert clone.snapshot()["log"] == plan.snapshot()["log"]
    assert clone.fire("cos.put", "warm") is None   # hit 2: unscheduled


# ---------------------------------------------------------------------------
# worker lifecycle hygiene
# ---------------------------------------------------------------------------

def test_close_reaps_workers_and_segments(tmp_path):
    before = _shm_segments()
    st = make_host(2, spill_dir=str(tmp_path / "spill"))
    pids = list(st.worker_pids())
    assert all(isinstance(p, int) for p in pids)
    assert len(_shm_segments() - before) == 4   # 2 rings x 2 shards
    st.put("k", b"k" * 9_000)
    assert st.close() is True
    assert _pids_gone(pids)
    assert _shm_segments() - before == set()


def test_abandoned_store_reaped_by_finalizer(tmp_path):
    """No stray processes or /dev/shm segments may survive a store the
    caller simply dropped (satellite: atexit/finalizer orphan reaping;
    the same hook runs at interpreter exit for still-referenced ones)."""
    before = _shm_segments()
    st = make_host(2, spill_dir=str(tmp_path / "spill"))
    pids = list(st.worker_pids())
    st.put("k", b"k" * 9_000)
    del st
    gc.collect()
    assert _pids_gone(pids)
    assert _shm_segments() - before == set()


_EXIT_CHILD = """
import json, sys
from multiprocessing import forkserver
from repro.core import Clock, ProcessShardedStore, StoreConfig
from repro.core.ec import ECConfig
cfg = StoreConfig(ec=ECConfig(k=4, p=2), function_capacity=8 << 20,
                  fragment_bytes=1 << 20, spill_dir=None)
st = ProcessShardedStore(cfg, num_shards=2, clock=Clock(),
                         cos_root=sys.argv[1])
st.put("k", b"k" * 9_000)
pids = list(st.worker_pids()) + [forkserver._forkserver._forkserver_pid]
if sys.argv[2] == "close":
    assert st.close()
print(json.dumps(pids), flush=True)
"""


@pytest.mark.parametrize("how", ["close", "abandon"])
def test_interpreter_exit_leaves_no_process(tmp_path, how):
    """Once an interpreter that used the process host has exited, none
    of its workers and not its forkserver is still running: the exit
    hook reaps what was left open, then stops the forkserver and waits
    for it rather than leaving it to notice the exit afterwards."""
    import json
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run(
        [sys.executable, "-c", _EXIT_CHILD, str(tmp_path / "cos"), how],
        env=env, capture_output=True, text=True, check=True, timeout=120)
    pids = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(pids) == 3 and all(isinstance(p, int) for p in pids)
    for pid in pids:                 # gone now, not after a grace period
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)


def test_close_escalates_past_stuck_worker(tmp_path):
    """A worker that cannot answer its close RPC (SIGSTOPped here) must
    not hold the host hostage: the shared deadline expires and reaping
    escalates to terminate/kill."""
    st = make_host(2, spill_dir=str(tmp_path / "spill"))
    pids = list(st.worker_pids())
    os.kill(pids[0], signal.SIGSTOP)
    try:
        t0 = time.monotonic()
        ok = st.close(deadline_s=2.0)
        elapsed = time.monotonic() - t0
    finally:
        try:
            os.kill(pids[0], signal.SIGCONT)
        except ProcessLookupError:
            pass
    assert ok is False               # the stuck shard didn't confirm
    assert elapsed < 60.0
    assert _pids_gone(pids)


def test_dead_worker_raises_shard_worker_died(tmp_path):
    st = make_host(2, spill_dir=str(tmp_path / "spill"))
    try:
        keys = {st.router.shard_of(f"k{i}"): f"k{i}" for i in range(32)}
        st.simulate_crash(shard=0)
        with pytest.raises(ShardWorkerDied):
            st.put(keys[0], b"x" * 9_000)
        # in-flight futures fail fast instead of hanging; survivors OK
        assert st.put(keys[1], b"y" * 9_000) == 1
        assert st.workers_alive() == [False, True]
        snap = st.snapshot_metadata()
        assert snap["health"]["state"] == "SHARD_DOWN"
    finally:
        st.close()


# ---------------------------------------------------------------------------
# real-SIGKILL durability (cross-process test_shard_2pc)
# ---------------------------------------------------------------------------

def test_sigkill_worker_mid_2pc_prepared_ticket_swept(tmp_path):
    """THE tentpole scenario: a cross-shard put_many whose leader died
    after the commit decision became durable (both shards hold prepared
    tickets), then a REAL SIGKILL of one in-doubt worker. Survivors
    keep serving the old values, restart_shard replays the journal
    (prepared/<ticket> record included), and the sweep rolls the whole
    batch forward — zero PENDING keys, zero stranded tickets."""
    plan = FaultPlan(seed=1).add(
        FaultPoint(site="shard.leader_death", action="crash", hits=(2,)))
    st = make_host(2, spill_dir=str(tmp_path / "spill"), faults=plan)
    try:
        rng = np.random.default_rng(1)
        pre = cross_shard_batch(st, tag="k", rng=rng)
        assert all(v == 1 for v in st.put_many(pre).values())
        new = {k: rng.bytes(12_000) for k in pre}
        with pytest.raises(InjectedCrash):
            st.put_many(new)         # leader dies between the rounds
        tickets = st.indoubt_tickets()
        assert tickets
        # REAL kill of an in-doubt participant, prepared ticket live
        st.simulate_crash(shard=0)
        # survivors keep serving — and the batch is still invisible
        for k, v in pre.items():
            if st.router.shard_of(k) == 1:
                assert st.get(k) == v
        # respawn: journal replay + inherited sweep find the durable
        # decision and roll EVERY participant forward
        st.restart_shard(0)
        assert st.indoubt_tickets() == []
        for k, v in new.items():
            assert st.get(k) == v, f"in-doubt key {k} not rolled forward"
        # keyspace fully writable again — no PENDING residue anywhere
        assert all(v == 3 for v in st.put_many(
            {k: b"x" * 9_000 for k in pre}).values())
    finally:
        st.close()


def test_sigkill_mid_put_many_presumed_abort(tmp_path):
    """Kill a worker holding a prepared ticket whose decision was NEVER
    recorded: restart + sweep must presume abort — the batch stays
    invisible and its keys stay writable."""
    st = make_host(2, spill_dir=str(tmp_path / "spill"))
    try:
        rng = np.random.default_rng(2)
        pre = cross_shard_batch(st, tag="p", rng=rng)
        assert all(v == 1 for v in st.put_many(pre).values())
        sub = [(k, b"n" * 9_000) for k in pre
               if st.router.shard_of(k) == 0][:2]
        prep = st.shards[0].prepare_put_many_async(
            sub, ticket=901).result()
        assert prep is not None
        assert 901 in st.shards[0].indoubt_tickets()
        st.simulate_crash(shard=0)   # SIGKILL, ticket outstanding
        st.restart_shard(0)
        assert st.indoubt_tickets() == []
        for k, v in pre.items():
            assert st.get(k) == v, f"aborted batch leaked into {k}"
        out = st.put_many({k: b"w" * 9_000 for k, _ in sub})
        assert all(v >= 2 for v in out.values())
    finally:
        st.close()


def test_sigkill_under_concurrent_load_zero_acked_loss(tmp_path):
    """Client threads hammer PUTs while one worker is SIGKILLed
    mid-stream: every write that ACKED (put returned) must survive the
    restart; in-flight writes may fail but only with ShardWorkerDied."""
    st = make_host(2, spill_dir=str(tmp_path / "spill"))
    try:
        acked = {}
        alock = threading.Lock()
        errs = []

        def client(t):
            rng = np.random.default_rng(t)
            for i in range(12):
                k = f"w{t}-{i}"
                v = rng.bytes(10_000)
                try:
                    st.put(k, v)
                except ConnectionError:
                    continue         # killed mid-flight: never acked
                except Exception as e:                # noqa: BLE001
                    errs.append(e)
                    return
                with alock:
                    acked[k] = v
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(4)]
        for th in threads:
            th.start()
        time.sleep(0.25)             # let traffic build
        st.simulate_crash(shard=0)
        for th in threads:
            th.join()
        assert not errs
        st.restart_shard(0)
        lost = [k for k, v in acked.items() if st.get(k) != v]
        assert not lost, f"acked writes lost: {lost}"
        assert st.indoubt_tickets() == []
    finally:
        st.close()


def test_whole_host_crash_then_rebuild_zero_loss(tmp_path):
    """simulate_crash() of the whole host (every worker SIGKILLed) then
    a rebuild on the same spill + COS roots replays every shard's
    journal — the PR-4 restart contract, now across processes."""
    spill = str(tmp_path / "spill")
    cosr = str(tmp_path / "cos")
    st = make_host(2, spill_dir=spill, cos_root=cosr)
    rng = np.random.default_rng(3)
    acked = {f"r{i}": rng.bytes(11_000) for i in range(10)}
    for k, v in acked.items():
        assert st.put(k, v) == 1
    pids = list(st.worker_pids())
    st.simulate_crash()
    assert _pids_gone(pids)
    st2 = make_host(2, spill_dir=spill, cos_root=cosr)
    try:
        for k, v in acked.items():
            assert st2.get(k) == v, f"acked write {k} lost at restart"
        assert st2.indoubt_tickets() == []
    finally:
        st2.close()


def test_worker_fault_plan_fires_in_worker(tmp_path):
    """StoreConfig(faults=...) serializes into workers: a scheduled
    worker-side COS fault actually fires there (surfaced through the
    writeback health), proving the chaos plane crossed the boundary."""
    plan = FaultPlan(seed=5).add(
        FaultPoint(site="cos.put", action="transient", every=1,
                   times=1_000_000))
    st = make_host(1, spill_dir=str(tmp_path / "spill"), faults=plan)
    try:
        st.put("f0", b"f" * 9_000)   # acks from SMS+journal
        assert st.get("f0") == b"f" * 9_000
        ok = st.flush_writeback(timeout=3.0)
        assert ok is False           # the injected COS outage is real
        state = st.snapshot_metadata()["health"]["state"]
        assert state in ("DEGRADED_WRITEBACK", "OK")
    finally:
        st.close(flush=False)


@pytest.mark.parametrize("transport", ["shm", "tcp"])
def test_workers_pinned_off_chip(tmp_path, monkeypatch, transport):
    """A shard worker never opens the accelerator its parent holds: even
    when the environment it inherits asks JAX for a TPU, the worker pins
    JAX to the CPU before its store picks a codec, so it boots (here,
    with no TPU, an unpinned worker would fail to initialise JAX) and
    its codec is the host table."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    cfg = StoreConfig(ec=ECConfig(k=4, p=2), function_capacity=8 * MB,
                      fragment_bytes=1 * MB, spill_dir=None)
    st = ProcessShardedStore(cfg, num_shards=2, clock=Clock(),
                             cos_root=str(tmp_path / "cos"),
                             start_method="spawn", transport=transport)
    try:
        vals = {f"k{i}": os.urandom(20_000 + i) for i in range(6)}
        st.put_many(list(vals.items()))
        assert st.get_many(list(vals)) == vals
        shards = st.snapshot_metadata()["shards"]
        assert [s["codec"]["backend"] for s in shards] == ["numpy"] * 2
    finally:
        assert st.close()
