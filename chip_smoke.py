#!/usr/bin/env python3
"""Smoke test of the store's main path on one TPU chip.

    python chip_smoke.py [--seed N]

One process holds the chip and drives three phases through the store's
public entry points. Every payload and parameter is generated from
--seed.

  a. store: a paper-default InfiniStore (RS(10+2), 1536 MB functions,
     200 MB fragments) over a disk COS with the spill journal on loads
     about 1 GiB in the paper's large-object regime, reads every object
     back against a dict oracle, checks the stored parity of one object
     per size class against the numpy codec, reads through parity rows
     on the paper's no-recovery configuration (so the k x k decode runs
     on the chip), then survives simulate_crash() and a rebuild on the
     same journal and COS.
  b. checkpoint: qwen1.5-0.5b parameters initialised on the chip at full
     width are saved through an InfiniStore and restored byte for byte.
  c. process host: a 2-shard ProcessShardedStore started from this
     process serves PUT/GET while the chip stays with this process.

Each served window prints one line: wall time, MiB/s, objects checked,
the device kind, and how many XLA programs were built in it (0 once
the codec is warm). The last line is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}};
a failed check exits non-zero without it. Exits 2 when JAX has no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

MB = 1024 * 1024
# the paper's large-object regime: (object bytes, count), ~1 GiB in all
STORE_CLASSES = ((100 * MB, 8), (10 * MB, 16), (1 * MB, 64),
                 (64 * 1024, 256))
# a slice of each class on the no-recovery store, read through parity
DEGRADED_CLASSES = ((100 * MB, 2), (10 * MB, 4), (1 * MB, 8),
                    (64 * 1024, 16))
# acked while writeback is paused, so only the journal holds them
UNFLUSHED_CLASSES = ((10 * MB, 4), (64 * 1024, 16))
HOST_CLASSES = ((10 * MB, 6),)
BATCH_BYTES = 128 * MB               # payload per GET batch


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def tpu_device():
    """The first JAX device, which must be a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this test runs only on a TPU",
              file=sys.stderr)
        sys.exit(2)
    return dev


class CompileCounter:
    """Counts XLA programs built in this process (each jit cache miss
    lowers one module, whether it then compiles or loads from the
    persistent cache)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


class Window:
    """Wall time and programs built between `start` and `stop`."""

    def __init__(self, compiles: CompileCounter):
        self._compiles = compiles
        self._t0, self._c0 = time.perf_counter(), compiles.n
        self.seconds = self.built = None

    def stop(self) -> "Window":
        if self.seconds is None:
            self.seconds = time.perf_counter() - self._t0
            self.built = self._compiles.n - self._c0
        return self


class Report:
    """Prints one line per window; a served window must build nothing."""

    def __init__(self, kind: str, compiles: CompileCounter):
        self.kind = kind
        self.compiles = compiles

    def start(self) -> Window:
        return Window(self.compiles)

    def line(self, phase: str, window: str, w: Window = None, *,
             served: bool = True, **fields) -> None:
        rec = {"phase": phase, "window": window, "device_kind": self.kind}
        if w is not None:
            w.stop()
            rec.update(seconds=w.seconds, compiles=w.built)
            if fields.get("bytes"):
                rec["MiB_per_s"] = fields["bytes"] / MB / w.seconds
        rec.update(fields)
        print(json.dumps(rec), flush=True)
        if w is not None and served:
            check(w.built == 0, f"{phase}/{window}: {w.built} programs "
                                f"compiled after warm-up")


def make_objects(rng, classes, prefix: str):
    """key -> read-only uint8 view of one random buffer (the oracle)."""
    import numpy as np
    total = sum(size * n for size, n in classes)
    blob = np.frombuffer(rng.bytes(total), np.uint8)
    objs, off = {}, 0
    for size, n in classes:
        for i in range(n):
            objs[f"{prefix}/{size}/{i:04d}"] = blob[off:off + size]
            off += size
    return objs


def by_class(objs):
    groups = {}
    for key, val in objs.items():
        groups.setdefault(val.size, []).append(key)
    return groups


def put_objects(store, objs):
    """One put_many_async per size class, all in flight together;
    returns {key: version} once every PUT acked."""
    futs = [store.put_many_async([(k, objs[k]) for k in keys])
            for keys in by_class(objs).values()]
    vers = {}
    for fut in futs:
        vers.update(fut.result())
    check(sorted(vers) == sorted(objs), "PUT acked a different key set")
    return vers


def get_objects(store, keys, sizes):
    """Batched array GETs of about BATCH_BYTES each, all in flight."""
    batches, cur, cur_bytes = [], [], 0
    for key in keys:
        cur.append(key)
        cur_bytes += sizes[key]
        if cur_bytes >= BATCH_BYTES:
            batches.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        batches.append(cur)
    futs = [store.get_many_arrays_async(b) for b in batches]
    out = {}
    for fut in futs:
        out.update(fut.result())
    return out


def check_equal(got, objs, what: str) -> int:
    import numpy as np
    for key, want in objs.items():
        val = got.get(key)
        check(val is not None, f"{what}: {key} missing")
        check(np.array_equal(np.asarray(val, np.uint8), want),
              f"{what}: {key} differs from the oracle")
    return len(objs)


def nbytes_of(objs) -> int:
    return sum(v.size for v in objs.values())


def store_phase(rep: Report, rng, workdir: str, classes=STORE_CLASSES,
                degraded_classes=DEGRADED_CLASSES,
                unflushed_classes=UNFLUSHED_CLASSES) -> None:
    from repro.core import InfiniStore, StoreConfig
    from repro.core.ec import RSCodec

    cos_root = os.path.join(workdir, "cos")
    spill = os.path.join(workdir, "spill")
    cfg = StoreConfig(spill_dir=spill)
    store = InfiniStore(cfg, cos_root=cos_root)
    crashed = False
    try:
        check(store.codec.backend == "pallas",
              f"store codec is {store.codec.backend!r}, not the kernel")
        w = rep.start()
        store.codec.warmup()
        rep.line("store", "codec_warmup", w, served=False)

        objs = make_objects(rng, classes, "obj")
        sizes = {k: v.size for k, v in objs.items()}
        total = nbytes_of(objs)
        w = rep.start()
        vers = put_objects(store, objs)
        rep.line("store", "put_ack", w, bytes=total, objects=len(objs))
        w = rep.start()
        check(store.flush_writeback(), "flush_writeback failed")
        rep.line("store", "flush_writeback", w, bytes=total,
                 objects=len(objs))

        w = rep.start()
        got = get_objects(store, list(objs), sizes)
        w.stop()
        rep.line("store", "get", w, bytes=total, objects=len(objs),
                 checked=check_equal(got, objs, "GET"))
        del got

        # stored parity rows (encoded on the chip) against the numpy codec
        host = RSCodec(cfg.ec, backend="numpy")
        checked = 0
        for keys in by_class(objs).values():
            key = keys[0]
            want = host.encode_many([objs[key]], as_arrays=True)[0]
            for idx in range(cfg.ec.k, cfg.ec.n):
                raw = store.cos.get(f"chunk/{key}|{vers[key]}/f0#{idx}")
                check(raw is not None, f"parity chunk {idx} of {key} absent")
                check(bytes(raw) == want[idx].tobytes(),
                      f"parity chunk {idx} of {key} differs from numpy")
            checked += 1
        rep.line("store", "parity_vs_numpy", checked=checked)

        degraded_phase(rep, rng, workdir, degraded_classes)

        # acked while writeback is paused: only the journal holds these
        extra = make_objects(rng, unflushed_classes, "unflushed")
        store.pause_writeback()
        w = rep.start()
        put_objects(store, extra)
        rep.line("store", "put_ack_unflushed", w, bytes=nbytes_of(extra),
                 objects=len(extra))
        objs.update(extra)
        sizes.update({k: v.size for k, v in extra.items()})
        check(store.simulate_crash() == spill, "spill dir moved")
        crashed = True
    finally:
        if not crashed:
            store.close()

    w = rep.start()
    store = InfiniStore(cfg, cos_root=cos_root)
    try:
        replayed = store.stats.spill_replayed_writes
        rep.line("store", "rebuild_replay", w, replayed_writes=replayed)
        check(replayed > 0, "the rebuild replayed nothing from the journal")
        w = rep.start()
        got = get_objects(store, list(objs), sizes)
        w.stop()
        rep.line("store", "get_after_crash", w, bytes=nbytes_of(objs),
                 objects=len(objs),
                 checked=check_equal(got, objs, "GET after crash"))
    finally:
        store.close()


def degraded_phase(rep: Report, rng, workdir: str, classes) -> None:
    """The paper's no-recovery ablation (StoreConfig(enable_recovery=
    False), Fig. 22/23): once a function is reclaimed, GETs rebuild its
    chunks from parity rows, so each decode inverts a survivor matrix and
    runs the k x k product on the chip."""
    from repro.core import InfiniStore, StoreConfig

    cfg = StoreConfig(enable_recovery=False,
                      spill_dir=os.path.join(workdir, "spill-snr"))
    store = InfiniStore(cfg, cos_root=os.path.join(workdir, "cos-snr"))
    try:
        objs = make_objects(rng, classes, "degraded")
        vers = put_objects(store, objs)
        check(store.flush_writeback(), "flush_writeback failed")
        row0 = {k: f"{k}|{vers[k]}/f0#0" for k in objs}
        fid = store.chunk_map[row0[next(iter(objs))]]
        store.inject_failure(fid)
        lost = sum(1 for ck in row0.values() if store.chunk_map.get(ck) == fid)
        inv0 = store.codec.cache_info()["inversions"]
        w = rep.start()
        got = get_objects(store, list(objs),
                          {k: v.size for k, v in objs.items()})
        w.stop()
        inversions = store.codec.cache_info()["inversions"] - inv0
        rep.line("store", "get_through_parity", w, bytes=nbytes_of(objs),
                 objects=len(objs),
                 checked=check_equal(got, objs, "degraded GET"),
                 data_rows_lost=lost, inversions=inversions)
        check(lost > 0 and inversions > 0,
              "no GET decoded through parity rows")
    finally:
        store.close()


def checkpoint_phase(rep: Report, seed: int, workdir: str,
                     cfg=None) -> None:
    import jax
    import numpy as np

    from repro.checkpoint import Checkpointer
    from repro.configs import get_config
    from repro.core import InfiniStore, StoreConfig
    from repro.models import build_model

    cfg = cfg if cfg is not None else get_config("qwen1.5-0.5b")
    dev = jax.devices()[0]
    w = rep.start()
    params = build_model(cfg).init_params(jax.random.PRNGKey(seed))
    leaves = jax.block_until_ready(jax.tree_util.tree_leaves(params))
    check(all(leaf.devices() == {dev} for leaf in leaves),
          "parameters are not on the chip")
    nbytes = sum(leaf.nbytes for leaf in leaves)
    rep.line("checkpoint", "init_params", w, served=False, arch=cfg.name,
             layers=cfg.num_layers, d_model=cfg.d_model,
             vocab=cfg.vocab_size, dtype=cfg.dtype,
             params=sum(leaf.size for leaf in leaves), param_bytes=nbytes)

    store = InfiniStore(
        StoreConfig(spill_dir=os.path.join(workdir, "spill-ckpt")),
        cos_root=os.path.join(workdir, "cos-ckpt"))
    try:
        ckpt = Checkpointer(store)
        w = rep.start()
        ckpt.save(1, params)
        rep.line("checkpoint", "save", w, bytes=nbytes, objects=len(leaves))
        w = rep.start()
        restored = ckpt.restore(1, like=params)
        rep.line("checkpoint", "restore", w, bytes=nbytes,
                 objects=len(leaves))
    finally:
        store.close()
    # back onto the chip, then every leaf compared byte for byte
    back = jax.tree_util.tree_leaves(jax.device_put(restored, dev))
    checked = 0
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(params)[0], back):
        w8, g8 = np.asarray(want), np.asarray(got)
        check(w8.dtype == g8.dtype and w8.shape == g8.shape
              and np.array_equal(w8.reshape(-1).view(np.uint8),
                                 g8.reshape(-1).view(np.uint8)),
              f"restored leaf {jax.tree_util.keystr(path)} differs")
        checked += 1
    rep.line("checkpoint", "compare_on_chip", checked=checked)


def child_processes():
    """pid -> command line of every live child of this process."""
    me, out = os.getpid(), {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid == me:
                with open(f"/proc/{name}/cmdline", "rb") as f:
                    out[int(name)] = f.read().replace(b"\0", b" ").decode()
        except (OSError, ValueError, IndexError):
            continue                 # exited while we looked
    return out


def pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    return True


def process_host_phase(rep: Report, rng, workdir: str,
                       classes=HOST_CLASSES) -> None:
    from repro.core.host import ProcessShardedStore, stop_forkserver

    store = ProcessShardedStore(num_shards=2,
                                cos_root=os.path.join(workdir, "cos-host"))
    pids = list(store.worker_pids())
    try:
        objs = make_objects(rng, classes, "host")
        w = rep.start()
        put_objects(store, objs)
        rep.line("process_host", "put_ack", w, bytes=nbytes_of(objs),
                 objects=len(objs))
        w = rep.start()
        got = get_objects(store, list(objs),
                          {k: v.size for k, v in objs.items()})
        w.stop()
        backends = [s["codec"]["backend"]
                    for s in store.snapshot_metadata()["shards"]]
        rep.line("process_host", "get", w, bytes=nbytes_of(objs),
                 objects=len(objs),
                 checked=check_equal(got, objs, "process-host GET"),
                 shard_codecs=backends)
        check(backends == ["numpy"] * 2,
              f"shard workers run codecs {backends}, not the host table")
    finally:
        closed = store.close()
        stop_forkserver()
    check(closed, "the process host did not close cleanly")
    # nothing the host started may outlive this process; the stdlib's
    # resource tracker is the one helper that exits with the interpreter
    workers = [pid for pid in pids if pid_alive(pid)]
    left = {pid: cmd for pid, cmd in child_processes().items()
            if "multiprocessing.resource_tracker" not in cmd}
    rep.line("process_host", "stopped", workers_alive=len(workers),
             children_left=len(left))
    check(not workers and not left,
          f"processes still running: workers {workers}, children {left}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = tpu_device()
    import jax
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "jax": jax.__version__,
                      "compile_cache": enable_compile_cache(),
                      "seed": args.seed}), flush=True)
    rep = Report(dev.device_kind, CompileCounter())
    rng = np.random.default_rng(args.seed)
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        store_phase(rep, rng, workdir)
        checkpoint_phase(rep, args.seed, workdir)
        process_host_phase(rep, rng, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
