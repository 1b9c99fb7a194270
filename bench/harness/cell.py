"""One run of one cell: set-up, the measured window, the check, the
metrics and the result line.

The store is built from the configuration's `store` fields (StoreConfig's
own names), with its spill journal on disk inside the run's work
directory and its COS in this process's memory, standing for the remote
object store, which outlives a crash of the store's daemon.
"""
from __future__ import annotations

import contextlib
import os
import re
import shutil
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.harness import devtrace, driver, peaks, sizelaw, stats, verify
from bench.harness.spec import ROOT, Cell, load_reader

MiB = 1024 * 1024
KERNELS = ("rs_gf256_bitsliced",)
SPAN_CAPACITY = 1 << 20
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
PUT_KINDS = ("put", "update", "save")
CONTROLS = ("journal_off",)
# the check: GET answers are held for it, at first 1 in HOLD_EVERY, thinned
# evenly over the window to stay within HOLD_BYTES beyond the largest
# object (`driver.HeldAnswers`); parity rows are compared for up to
# PARITY_BYTES of sampled payload
HOLD_EVERY = 8
HOLD_BYTES = 512 * MiB
PARITY_BYTES = 256 * MiB


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class RunRecord:
    """What a per-layer reader (`bench/layers/<metric>.py`) reads."""
    cell: str
    window_s: float
    put_bytes: int
    get_bytes: int
    spans: List[Dict]                       # the plane's finished spans
    histograms: Dict[str, List[int]]        # site -> bucket counts
    bucket_upper_us: Callable[[int], float]
    trace: Optional[devtrace.Summary]
    peaks: Dict[str, float]

    def span_seconds(self, site: str) -> Optional[float]:
        """Summed seconds of the window's spans of one site; None where
        there is none."""
        durs = [s["dur_us"] or 0.0 for s in self.spans if s["site"] == site]
        return sum(durs) * 1e-6 if durs else None


class CompileCounter:
    """Programs lowered in this process (copied from the program's
    `chip_smoke.py`): each jit cache miss lowers one module, whether it
    then compiles or loads from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def _device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"JAX sees {len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind}); the cell needs {chips} TPU")
    return devs[0], len(devs)


def _compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where JAX_COMPILATION_CACHE_DIR says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_store(config: Dict, workdir: Path, cos, *, obs=None,
                control: Optional[str] = None):
    from repro.core import InfiniStore, StoreConfig
    from repro.core.ec import ECConfig
    fields = dict(config["store"])
    ec = ECConfig(**fields.pop("ec"))
    spill = None if control == "journal_off" else str(workdir / "spill")
    return InfiniStore(StoreConfig(ec=ec, spill_dir=spill, obs=obs,
                                   **fields), cos=cos)


class VersionLog:
    """A frontend in front of the store for the checkpointer: records the
    version each batched PUT acked, and passes everything through."""

    def __init__(self, store):
        self._store = store
        self.versions: Dict[str, int] = {}

    def __getattr__(self, name):
        return getattr(self._store, name)

    def put_many_async(self, items, **kw):
        fut = self._store.put_many_async(items, **kw)
        fut.add_done_callback(self._record)
        return fut

    def _record(self, fut) -> None:
        if fut.exception() is None:
            self.versions.update(fut.result())


def leaf_names(params) -> Dict[str, object]:
    """Leaf name -> leaf, named as the checkpoint's keys name them: the
    tree path's keys joined by '/'."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): leaf for path, leaf in flat}


def init_params(config: Dict, seed: int, small: Optional[Dict] = None):
    """The model's weights, made on the device from the seed in one
    jitted call, in the dtype they are trained in."""
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.models import build_model
    model_cfg = get_config(config["model"]["repro_name"])
    published = config["model"]["published"]
    mine = {"hidden_size": model_cfg.d_model,
            "num_hidden_layers": model_cfg.num_layers,
            "num_attention_heads": model_cfg.num_heads,
            "num_key_value_heads": model_cfg.num_kv_heads,
            "intermediate_size": model_cfg.d_ff,
            "vocab_size": model_cfg.vocab_size,
            "tie_word_embeddings": model_cfg.tie_embeddings,
            "torch_dtype": model_cfg.dtype}
    differ = {k: (v, published[k]) for k, v in mine.items()
              if published.get(k) != v}
    if differ:
        raise ValueError(f"the program's {model_cfg.name} differs from the "
                         f"published config: {differ}")
    if small:                                  # CPU tests only
        model_cfg = dataclasses.replace(model_cfg, **small)
    model = build_model(model_cfg)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    params = jax.jit(model.init_params)(key)
    return jax.block_until_ready(params)


def stamper():
    """A jitted call `stamp(params, i)` that gives save i bytes of its own,
    as a training step between saves would: every leaf's bits XORed with
    i + 1 (so saves i and i - step_ring differ in every element)."""
    import jax
    import jax.numpy as jnp

    def one(x, i):
        u = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
        bits = jax.lax.bitcast_convert_type(x, u)
        return jax.lax.bitcast_convert_type(bits ^ (i + 1).astype(u),
                                            x.dtype)

    return jax.jit(lambda params, i: jax.tree.map(
        lambda x: one(x, i), params))


def e2e_value(name: str, window: driver.Window, setup_s: float
              ) -> Optional[float]:
    """An end-to-end metric by its name: `setup_s`, `<op>_MiBps` (bytes
    of every request of that class done in the window over its whole
    length) or `<op>_p<q>_ms` (the q-th percentile of every request's
    latency; a failed one counts as infinitely late)."""
    if name == "setup_s":
        return setup_s
    m = re.fullmatch(r"(put|get)_MiBps", name)
    if m:
        kinds = PUT_KINDS if m.group(1) == "put" else ("get",)
        done = sum(op.size for op in window.ops
                   if op.kind in kinds and op.ok)
        return stats.rate_mib_s(done, window.seconds)
    m = re.fullmatch(r"(put|get)_p([0-9.]+)_ms", name)
    if m:
        kinds = PUT_KINDS if m.group(1) == "put" else ("get",)
        lat = [(op.t1 - op.t0) * 1e3 if op.ok else float("inf")
               for op in window.ops if op.kind in kinds]
        return stats.percentile(lat, float(m.group(2))) if lat else None
    raise ValueError(f"no rule for the end-to-end metric {name!r}")


def _thread_cpu() -> Dict[str, float]:
    """CPU seconds (user and system) each live thread of this process has
    used so far, by thread name, from /proc."""
    tick = os.sysconf("SC_CLK_TCK")
    out: Dict[str, float] = {}
    for t in threading.enumerate():
        try:
            with open(f"/proc/self/task/{t.native_id}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, TypeError):
            continue
        out[t.name] = (int(fields[11]) + int(fields[12])) / tick
    return out


def _write_bytes() -> Optional[int]:
    """Bytes this process has passed to write calls so far."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("wchar:"):
                    return int(line.split()[1])
    except OSError:
        return None
    return None


@dataclass
class Options:
    require_tpu: bool = True
    compile_cache: bool = True
    control: Optional[str] = None
    # CPU tests only: breaks the store under test, to see `correct` fail
    fault: Optional[Callable] = None
    # CPU tests only: smaller traffic, data and model
    overrides: Dict = field(default_factory=dict)
    workdir: Optional[Path] = None
    keep_trace: Optional[Path] = None


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, opts: Options = Options(),
        log: Callable[[str], None] = lambda s: None) -> Dict:
    """One run; returns the result line's object."""
    dev, count = _device(cell.chips, opts.require_tpu)
    import jax
    if opts.compile_cache:
        log(f"compile cache: {_compile_cache()}")
    marks = [("device", time.perf_counter())]
    if opts.control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {opts.control!r}")
    compiles = CompileCounter()
    workdir = opts.workdir or ROOT / ".bench_work" / cell.name
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    written0 = _write_bytes()
    traffic = {**cell.traffic, **opts.overrides.get("traffic", {})}
    config = cell.config
    kind = traffic["kind"]
    key_sizes: List[int] = []
    if kind == "closed_loop":
        law = sizelaw.law_params(config["object_sizes"])
        if any(k in traffic["mix"] for k in ("get", "update")):
            key_sizes = driver.working_set(law, int(opts.overrides.get(
                "dataset_bytes", config["dataset_bytes"])))
        schedule = driver.Schedule(traffic, law, seed, key_sizes)
        pool = driver.PayloadPool(seed, schedule.max_size())

    from repro.core.clock import Clock
    from repro.core.cos import COS
    from repro.obs import ObsPlane
    from repro.obs.metrics import bucket_upper_us
    plane = ObsPlane(enabled=False, name=cell.name,
                     span_capacity=SPAN_CAPACITY) if trace else None
    cos = COS(Clock())
    store = build_store(config, workdir, cos, obs=plane,
                        control=opts.control)
    marks.append(("data and store", time.perf_counter()))
    try:
        if opts.fault is not None:
            opts.fault(store)
        k, p = config["store"]["ec"]["k"], config["store"]["ec"]["p"]
        if store.codec.backend == "pallas":
            from repro.kernels.rs_gf256.kernel import warmup_bitsliced
            warmup_bitsliced(p, k, interpret=False)   # encode geometry
        elif opts.require_tpu:
            raise NoChip(f"the store's codec is {store.codec.backend!r}")
        marks.append(("kernel warm-up", time.perf_counter()))
        versions = driver.Versions()
        if kind == "closed_loop" and key_sizes:
            _preload(store, pool, key_sizes, versions)
        if kind == "checkpoint":
            from repro.checkpoint import Checkpointer
            params = init_params(config, seed,
                                 opts.overrides.get("model"))
            nbytes = sum(int(x.nbytes) for x in jax.tree.leaves(params))
            stamp = stamper()
            jax.block_until_ready(stamp(params, np.uint32(0)))
            front = VersionLog(store)
            ckpt = Checkpointer(front)
        marks.append(("preload or weights", time.perf_counter()))

        log_dir = workdir / "trace"
        if trace:
            popts = jax.profiler.ProfileOptions()
            popts.python_tracer_level = 0
            jax.profiler.start_trace(str(log_dir), profiler_options=popts)
            plane.enabled = True
            cpu0 = _thread_cpu()
        annotate = jax.profiler.TraceAnnotation if trace else \
            (lambda name: contextlib.nullcontext())
        c0 = compiles.n
        setup_s = time.perf_counter() - t_process
        log("set-up: " + ", ".join(
            f"{what} {t - prev:.2f} s" for (what, t), prev in
            zip(marks, [t_process] + [t for _, t in marks])))
        with annotate(devtrace.WINDOW_ANNOTATION):
            if kind == "closed_loop":
                held = driver.HeldAnswers(
                    seed, HOLD_EVERY, HOLD_BYTES + schedule.max_size())
                window = driver.closed_loop(
                    store, pool, schedule, versions,
                    clients=int(traffic["clients"]), seconds=seconds,
                    held=held, annotate=annotate)
            else:
                window = driver.checkpoint_loop(
                    ckpt, params, stamp, nbytes, seconds=seconds,
                    step_ring=int(traffic["step_ring"]), annotate=annotate)
        built = compiles.n - c0
        summary = None
        if trace:
            plane.enabled = False
            cpu = {name: t - cpu0.get(name, 0.0)
                   for name, t in _thread_cpu().items()}
            jax.profiler.stop_trace()
            log("CPU s in the window by thread: " + ", ".join(
                f"{n} {t:.2f}" for n, t in sorted(
                    cpu.items(), key=lambda kv: -kv[1])[:8]))
            tr = devtrace.load_xplane(str(log_dir))
            if opts.keep_trace is not None:
                opts.keep_trace.mkdir(parents=True, exist_ok=True)
                devtrace.dump_json(tr, str(opts.keep_trace /
                                           f"{cell.name}.json"))
            summary = devtrace.summarize(tr, KERNELS) \
                if tr.device_planes() else None
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) \
            if hasattr(dev, "memory_stats") else 0

        # ---- the check, once the window has closed -----------------------
        checks: Dict[str, float] = {"compiles": built}
        sample_n = int(traffic["sample_objects"])
        frag = int(config["store"]["fragment_bytes"])
        if kind == "closed_loop":
            content = lambda key, gen: pool.content(  # noqa: E731
                key, gen, size_of[key])
            size_of = {op.key: op.size for op in window.ops}
            size_of.update(enumerate(key_sizes))
            latest = _latest(window, versions, key_sizes)
            if any(op.kind == "get" for op in window.ops):
                log(_held_spread(window))
            checks["answers"] = verify.answers(window.held, versions,
                                               content)
            window.held.clear()
            sample = _sample(latest, size_of, seed, sample_n)
            checks["unchecked"] = int(not sample)     # nothing was acked
            items = [(driver.key_name(key), content(key, latest[key][0]))
                     for key in sample]
            checks["readback"] = verify.readback(store, items)
            store.simulate_crash()
            store = None
            store = build_store(config, workdir, cos, control=opts.control)
            checks["lost_after_crash"] = verify.readback(store, items)
            store.flush_writeback()
            checks["parity"] = verify.parity(
                cos, [(driver.key_name(key), latest[key][1],
                       content(key, latest[key][0])) for key in sample],
                k=k, p=p, fragment_bytes=frag, budget_bytes=PARITY_BYTES)[0]
        else:
            # the newest acked save's state, as the chip holds it
            del params
            last, state = window.held[-1] if window.held else (None, {})
            window.held.clear()
            truth = {name: np.asarray(leaf)
                     for name, leaf in leaf_names(state).items()}
            del state
            checks["unchecked"] = int(last is None)
            last = last.key if last is not None else 0
            store.simulate_crash()
            store = None
            store = build_store(config, workdir, cos, control=opts.control)
            try:
                restored = Checkpointer(store).restore(last)
            except Exception as e:
                log(f"restore after the crash failed: {e!r}")
                restored = None
            checks["restore"] = verify.leaves_differ(restored, truth)
            del restored
            store.flush_writeback()
            checks["parity"] = verify.parity(
                cos, _shards(front.versions, truth, last,
                             ckpt.cfg.leaf_shard_bytes, ckpt.cfg.prefix,
                             seed, sample_n),
                k=k, p=p, fragment_bytes=frag, budget_bytes=PARITY_BYTES)[0]
    finally:
        if store is not None:
            store.close(flush=False)
        cos.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)

    put_bytes = sum(op.size for op in window.ops
                    if op.kind in PUT_KINDS and op.ok)
    get_bytes = sum(op.size for op in window.ops
                    if op.kind == "get" and op.ok)
    written = _write_bytes()
    log(f"window {window.seconds:.3f} s, {len(window.ops)} requests, "
        f"{put_bytes / MiB:.1f} MiB put, {get_bytes / MiB:.1f} MiB got, "
        f"programs built in it {built}, bytes the run wrote "
        f"{None if written is None else written - (written0 or 0)}")

    metrics: Dict[str, Dict] = {}
    if not trace:
        for m in cell.end_to_end:
            value = e2e_value(m["name"], window, setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        snap = plane.snapshot()
        if len(snap["spans"]) >= SPAN_CAPACITY:
            raise RuntimeError("the span ring overflowed in the window")
        rec = RunRecord(
            cell=cell.name, window_s=window.seconds, put_bytes=put_bytes,
            get_bytes=get_bytes, spans=snap["spans"],
            histograms={s: h["buckets"]
                        for s, h in snap["histograms"].items()},
            bucket_upper_us=bucket_upper_us, trace=summary,
            peaks=peaks.peaks(dev.device_kind) if dev.platform == "tpu"
            else {})
        for m in cell.per_layer:
            value = load_reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    limits = {name: 0 for name in checks}
    result = {
        "correct": all(checks[n] <= limits[n] for n in checks),
        "attempted": len(window.ops),
        "failed": sum(not op.ok for op in window.ops),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": count, "memory_peak_bytes": int(mem)},
    }
    if trace and summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["checks"] = {n: {"value": checks[n], "limit": limits[n]}
                        for n in checks}
    return result


def _preload(store, pool, key_sizes: List[int], versions) -> None:
    """Write the working set in batches of about 128 MiB, then wait until
    COS holds it all: the window starts on a settled store."""
    batch, nbytes, futs = [], 0, []
    for key, size in enumerate(key_sizes):
        batch.append((driver.key_name(key), pool.content(key, 0, size)))
        nbytes += size
        if nbytes >= 128 * MiB:
            futs.append(store.put_many_async(batch))
            batch, nbytes = [], 0
    if batch:
        futs.append(store.put_many_async(batch))
    for fut in futs:
        for name, ver in fut.result().items():
            if ver < 0:
                raise RuntimeError(f"preload of {name} failed")
            versions.preload(int(name.split("/")[1]), ver)
    if not store.flush_writeback():
        raise RuntimeError("preload did not reach COS")


def _held_spread(window) -> str:
    """Where in the window the GETs whose answers the check compares were
    issued, as shares of the window's length."""
    at = sorted((op.t0 - window.t_start) / window.seconds
                for op, _ in window.held)
    gets = sum(op.kind == "get" for op in window.ops)
    where = ", ".join(f"{q}% {at[min(len(at) - 1, len(at) * q // 100)]:.2f}"
                      for q in (0, 25, 50, 75, 100)) if at else "none"
    late = sum(t >= 0.5 for t in at)
    return (f"answers held: {len(at)} of {gets} GETs, {late} issued in the "
            f"window's second half; issue time by share of the window: "
            f"{where}")


def _latest(window, versions, key_sizes) -> Dict[int, tuple]:
    """key -> (generation, version) of the newest acked write."""
    out = {key: versions.latest(key) for key in range(len(key_sizes))}
    for op in window.ops:
        if op.kind == "put" and op.ok:
            out[op.key] = (0, op.version)
    return out


def _sample(latest: Dict[int, tuple], size_of: Dict[int, int], seed: int,
            n: int) -> List[int]:
    """n keys drawn from the seed, with the largest among them."""
    keys = sorted(latest)
    if not keys:
        return []
    rng = np.random.default_rng([seed & driver.MASK64, 0x5A3E])
    pick = set(rng.choice(keys, size=min(n, len(keys)),
                          replace=False).tolist())
    pick.add(max(keys, key=lambda key: size_of[key]))
    return sorted(pick)


def _shards(versions: Dict[str, int], truth: Dict[str, np.ndarray],
            step: int, shard_bytes: int, prefix: str, seed: int, n: int):
    """(key, version, bytes) of n leaf shards of the last step, drawn from
    the seed, with the largest leaf's first shard among them."""
    head = f"{prefix}/{step:08d}/"
    keys = sorted(k for k in versions if k.startswith(head))
    rng = np.random.default_rng([seed & driver.MASK64, 0x5A3F])
    pick = set(rng.choice(len(keys), size=min(n, len(keys)),
                          replace=False).tolist()) if keys else set()
    out = []
    biggest = max(truth, key=lambda name: truth[name].nbytes)
    for i, key in enumerate(keys):
        name, _, shard = key[len(head):].rpartition("/s")
        if i not in pick and not (name == biggest and shard == "0"):
            continue
        flat = truth[name].reshape(-1).view(np.uint8)
        lo = int(shard) * shard_bytes
        out.append((key, versions[key], flat[lo:lo + shard_bytes]))
    return out
