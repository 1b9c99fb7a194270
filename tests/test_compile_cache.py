"""Placement of JAX's persistent compilation cache (repro.launch.
compile_cache). Only the directory choice is tested: the cache itself is
never switched on in tests."""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache as cc


def test_environment_variable_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "cache"))
    assert cc.compile_cache_dir() == str(tmp_path / "cache")


def test_fixed_path_inside_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    root = Path(__file__).resolve().parents[1]       # the checkout
    assert cc.compile_cache_dir() == str(root / ".jax_cache")
    assert cc.compile_cache_dir() == cc.compile_cache_dir()


def test_same_path_in_every_process(monkeypatch):
    """No pid, time or temp name in the path: another process, started
    later, resolves the same directory."""
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    code = ("from repro.launch.compile_cache import compile_cache_dir; "
            "print(compile_cache_dir())")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    runs = {subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, check=True,
                           timeout=60).stdout.strip() for _ in range(2)}
    assert runs == {cc.compile_cache_dir()}
