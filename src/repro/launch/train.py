"""Training launcher: real training loop with InfiniStore checkpointing.

On the CPU container this drives reduced configs end-to-end (the examples
use it); on a pod the same loop runs the full configs under
make_production_mesh(). Fault tolerance: periodic EC-coded checkpoints
through InfiniStore; on restart (or simulated failure) the loop resumes
from the latest recoverable step, and the deterministic data pipeline
replays the exact stream.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer, CheckpointConfig
from repro.configs import SHAPES_BY_NAME, ShapeConfig, get_config, reduced
from repro.configs.base import ModelConfig
from repro.core import Clock, InfiniStore, StoreConfig
from repro.core.ec import ECConfig
from repro.core.gc_window import GCConfig
from repro.data.pipeline import TokenPipeline
from repro.distributed.sharding import make_rules, set_global_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw


@dataclass
class TrainResult:
    steps: int
    final_loss: float
    losses: list
    wall_s: float
    restored_from: Optional[int] = None


def make_store_for_checkpoints(tmpdir: Optional[str] = None) -> InfiniStore:
    cfg = StoreConfig(
        ec=ECConfig(k=4, p=2),
        function_capacity=64 * 1024 * 1024,
        fragment_bytes=8 * 1024 * 1024,
        gc=GCConfig(gc_interval=3600.0),
    )
    return InfiniStore(cfg, clock=Clock(), cos_root=tmpdir)


def train(cfg: ModelConfig, shape: ShapeConfig, *, steps: int,
          seed: int = 0, num_microbatches: int = 1,
          checkpointer: Optional[Checkpointer] = None,
          checkpoint_every: int = 0, resume: bool = False,
          opt_cfg: Optional[adamw.AdamWConfig] = None,
          mesh=None) -> TrainResult:
    t0 = time.monotonic()
    model = build_model(cfg)
    if mesh is not None:
        set_global_rules(make_rules(cfg, mesh))
    opt_cfg = opt_cfg or adamw.AdamWConfig(lr=1e-3, warmup_steps=10)
    step_fn = jax.jit(make_train_step(model, opt_cfg),
                      donate_argnums=(0, 1))
    params = model.init_params(jax.random.PRNGKey(seed))
    opt_state = adamw.adamw_init(params)
    start = 0
    restored_from = None
    if resume and checkpointer is not None:
        latest = checkpointer.latest_step()
        if latest is not None:
            state = checkpointer.restore(latest,
                                         like={"params": params,
                                               "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            params = jax.tree.map(jnp.asarray, params)
            opt_state = jax.tree.map(jnp.asarray, opt_state)
            start = latest
            restored_from = latest
    pipe = TokenPipeline(cfg, shape, num_microbatches=num_microbatches,
                         seed=seed, start_step=start)
    losses = []
    for step in range(start, steps):
        batch = jax.tree.map(jnp.asarray, next(pipe))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if checkpointer is not None and checkpoint_every \
                and (step + 1) % checkpoint_every == 0:
            checkpointer.save(step + 1,
                              {"params": params, "opt": opt_state})
    return TrainResult(steps=steps, final_loss=losses[-1] if losses else 0.0,
                       losses=losses, wall_s=time.monotonic() - t0,
                       restored_from=restored_from)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    shape = ShapeConfig("cli", seq_len=args.seq_len,
                        global_batch=args.batch, kind="train")
    ckpt = None
    if args.checkpoint_every:
        ckpt = Checkpointer(make_store_for_checkpoints())
    res = train(cfg, shape, steps=args.steps, checkpointer=ckpt,
                checkpoint_every=args.checkpoint_every)
    print(f"trained {res.steps} steps in {res.wall_s:.1f}s; "
          f"loss {res.losses[0]:.3f} -> {res.final_loss:.3f}")


if __name__ == "__main__":
    main()
