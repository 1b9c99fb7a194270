"""The check that decides `correct`, driven through a whole run of each
cell, built from BENCHMARK.json by its name, on the CPU at a small size
(the look for a chip skipped): sound runs come out correct and yield
every end-to-end metric of the cell; the control (the store without its
journal) and each fault the cells can have, planted under the timed path,
come out not correct. The GET answers the check compares are a sample of
the whole window.

And `bench.run` prints no result and exits non-zero without a TPU."""
import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench.harness import spec
from bench.harness import cell as runner
from bench.harness import driver

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 77
SMALL = {
    "traffic": {"round": 16, "sizes": "body", "clients": 4},
    "dataset_bytes": 8 << 20,
    "model": {"num_layers": 2, "d_model": 64, "d_ff": 128,
              "vocab_size": 512, "num_heads": 4, "num_kv_heads": 4,
              "head_dim": 16},
}


CELLS = sorted(w["name"] for w in spec.load_benchmark()["workloads"])


def _run(name, tmp_path, trace=False, **kw):
    return runner.run(
        spec.load_cell(name), SEED, 0.5, trace, time.perf_counter(),
        runner.Options(require_tpu=False, compile_cache=False,
                       overrides=SMALL, workdir=tmp_path / "work", **kw))


def _unstored(store):
    store._put_many_impl = lambda items, **kw: {k: 1 for k, _ in items}


def _half(store):
    orig, n = store._put_many_impl, [0]

    def half(items, **kw):
        items = list(items)
        keep = [it for i, it in enumerate(items) if (n[0] + i) % 2 == 0]
        n[0] += len(items)
        out = orig(keep, **kw)
        return {k: out.get(k, 1) for k, _ in items}
    store._put_many_impl = half


def _stale(store):
    """Overwrites of a key already written are acked and dropped: the key
    keeps its older bytes."""
    orig, seen = store._put_many_impl, set()

    def stale(items, **kw):
        items = list(items)
        fresh = [it for it in items if it[0] not in seen]
        seen.update(k for k, _ in items)
        out = orig(fresh, **kw) if fresh else {}
        return {k: out.get(k, 1) for k, _ in items}
    store._put_many_impl = stale


def _altered_answer(store):
    orig = store._assemble

    def altered(pieces, size, as_arrays):
        val = orig(pieces, size, as_arrays)
        u8 = np.frombuffer(val, np.uint8).copy() if isinstance(val, bytes) \
            else np.array(val, np.uint8)
        if u8.size:
            u8[u8.size // 2] ^= 1
        return u8.tobytes() if isinstance(val, bytes) else u8
    store._assemble = altered


def _altered_parity(monkeypatch):
    from repro.core.ec import RSCodec
    orig = RSCodec._matmul

    def altered(self, G, X):
        out = orig(self, G, X)
        if G.shape[0] == self.cfg.p and out.size:
            out = out.copy()
            out[0, 0] ^= 1
        return out
    monkeypatch.setattr(RSCodec, "_matmul", altered)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tmp_path):
    res = _run(name, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    cell = spec.load_cell(name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = res["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        assert math.isfinite(got["value"]) and got["value"] > 0, got


def test_traced_get_run_reads_the_get_path_spans(tmp_path):
    # the device share needs a chip's trace; the two span readers do not
    res = _run("registry.get", tmp_path, trace=True)
    assert res["correct"], res["checks"]
    for name in ("get.daemon_s_per_GiB", "get.sms_sweep_p99_ms"):
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0, (name, value)


@pytest.mark.parametrize("name", CELLS)
def test_control_without_journal_is_not_correct(name, tmp_path):
    res = _run(name, tmp_path, control="journal_off")
    assert not res["correct"]
    assert (res["checks"].get("lost_after_crash")
            or res["checks"].get("restore"))["value"] > 0


@pytest.mark.parametrize("name,fault", [
    ("registry.put", "unstored"), ("registry.put", "half"),
    ("registry.put", "altered_answer"), ("registry.put", "altered_parity"),
    ("registry.get", "unstored"), ("registry.get", "altered_answer"),
    ("registry.get", "stale"),
    ("ckpt.save", "unstored"), ("ckpt.save", "half"), ("ckpt.save", "stale"),
    ("ckpt.save", "altered_parity"),
    ("registry.put-small", "unstored"), ("registry.put-small", "half"),
    ("registry.put-small", "altered_answer"),
    ("registry.put-small", "altered_parity"),
])
def test_fault_under_the_timed_path_is_not_correct(name, fault, tmp_path,
                                                   monkeypatch):
    plant = {"unstored": _unstored, "half": _half, "stale": _stale,
             "altered_answer": _altered_answer}.get(fault)
    if fault == "altered_parity":
        _altered_parity(monkeypatch)
    res = _run(name, tmp_path, fault=plant)
    assert not res["correct"], res["checks"]


def _offer_all(held, n, size):
    for i in range(n):
        held.offer(i, driver.Op("get", i % 7, 0, size, float(i)), i)
    return [op.key for op, _ in held.items()], [v for _, v in held.items()]


@pytest.mark.parametrize("n,size,budget", [
    (4000, 1 << 20, 64 << 20), (4000, 1 << 20, 8 << 20),
    (3000, 5 << 20, 400 << 20)])
def test_held_answers_sample_the_whole_window(n, size, budget):
    held = driver.HeldAnswers(SEED, 8, budget)
    _, kept = _offer_all(held, n, size)
    assert kept and len(kept) * size <= budget
    # the kept answers are exactly those the final rule picks, from the
    # whole sequence: as many from its second half as from its first
    rule = [i for i in range(n) if driver.mix64(SEED, i, 0x401D)
            % held.every == 0]
    assert sorted(kept) == rule
    late = sum(i >= n // 2 for i in kept)
    assert 0.3 <= late / len(kept) <= 0.7


def test_held_answers_are_drawn_from_the_seed():
    a = _offer_all(driver.HeldAnswers(SEED, 8, 32 << 20), 2000, 1 << 20)
    b = _offer_all(driver.HeldAnswers(SEED, 8, 32 << 20), 2000, 1 << 20)
    c = _offer_all(driver.HeldAnswers(SEED + 1, 8, 32 << 20), 2000,
                   1 << 20)
    assert a == b and a[1] != c[1]
    with pytest.raises(ValueError):
        driver.HeldAnswers(SEED, 6, 1 << 20)


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "-m", "bench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _printed_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            continue
    return False


def test_run_exits_without_a_tpu():
    proc = _cli(["--workload", "registry.put", "--seed", str(SEED),
                 "--seconds", "1", "--trace", "0"], ROOT)
    assert proc.returncode == 3 and not _printed_result(proc.stdout)
    proc = _cli(["--workload", "no.such.cell", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], ROOT)
    assert proc.returncode == 2 and not _printed_result(proc.stdout)


def test_run_without_the_program_prints_nothing(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(["--workload", "registry.put", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], tmp_path,
                {"PYTHONPATH": ""})
    assert proc.returncode != 0 and not _printed_result(proc.stdout)
