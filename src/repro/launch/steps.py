"""Step builders: train_step (grad-accumulation + ZeRO AdamW) and
serve_step (prefill / decode), with their in/out shardings.

These are the functions the multi-pod dry-run lowers and the examples run.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import (make_rules, set_global_rules,
                                        sharding_for, tree_shardings)
from repro.launch import specs as specs_lib
from repro.models.registry import Model, build_model
from repro.optim import adamw

PyTree = Any


def _axes_is_leaf(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


# --------------------------------------------------------------------------
# Train
# --------------------------------------------------------------------------

def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    batch leaves have a leading num_microbatches dim; gradients are
    accumulated in fp32 across a `lax.scan` so activation memory stays
    one-microbatch-deep.
    """
    def train_step(params, opt_state, batch):
        def loss_of(p, mb):
            loss, metrics = model.loss_fn(p, mb)
            return loss, metrics

        grad_fn = jax.value_and_grad(loss_of, has_aux=True)

        def micro(carry, mb):
            g_acc, loss_acc = carry
            (loss, _metrics), grads = grad_fn(params, mb)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            return (g_acc, loss_acc + loss), None

        n = jax.tree.leaves(batch)[0].shape[0]
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g_sum, loss_sum), _ = lax.scan(micro, (g0, 0.0), batch)
        grads = jax.tree.map(lambda g: g / n, g_sum)
        loss = loss_sum / n
        new_params, new_opt, om = adamw.adamw_update(
            opt_cfg, grads, opt_state, params)
        metrics = {"loss": loss, **om}
        return new_params, new_opt, metrics

    return train_step


def dp_size(mesh: Mesh) -> int:
    return mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)


def make_train_step_compressed(model: Model, opt_cfg: adamw.AdamWConfig,
                               mesh: Mesh):
    """Multi-pod train step with int8 error-feedback gradient exchange
    over the pod (DCN) axis — see optim/compression.py. The opt state
    carries the quantization-error tree under "err"; intra-pod (ICI)
    reductions stay full precision."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import (get_global_rules,
                                            set_global_rules)
    from repro.optim import compression

    def train_step(params, opt_state, batch):
        err = opt_state["err"]

        def per_pod(params, batch, err):
            def loss_of(p, mb):
                loss, metrics = model.loss_fn(p, mb)
                return loss, metrics

            grad_fn = jax.value_and_grad(loss_of, has_aux=True)

            def micro(carry, mb):
                g_acc, loss_acc = carry
                (loss, _m), grads = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                return (g_acc, loss_acc + loss), None

            n = jax.tree.leaves(batch)[0].shape[0]
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (g_sum, loss_sum), _ = lax.scan(micro, (g0, 0.0), batch)
            grads = jax.tree.map(lambda g: g / n, g_sum)
            loss = loss_sum / n
            # compressed cross-pod exchange (int8 on the DCN)
            grads, new_err = compression.psum_compressed(grads, "pod", err)
            loss = jax.lax.pmean(loss, "pod")
            return grads, new_err, loss

        b_spec = jax.tree.map(
            lambda x: P(None, "pod") if x.ndim >= 2 else P(), batch)
        g_spec = jax.tree.map(lambda _: P(), params)
        # inside the manual-pod region, activation constraints must not
        # mention the (now Manual) pod axis — swap the rules for tracing
        outer_rules = get_global_rules()
        if outer_rules is not None:
            inner = dict(outer_rules)
            inner["batch"] = "data"
            set_global_rules(inner)
        try:
            from repro.launch.mesh import shard_map
            grads, new_err, loss = shard_map(
                per_pod, mesh=mesh, axis_names={"pod"},
                in_specs=(g_spec, b_spec, g_spec),
                out_specs=(g_spec, g_spec, P()),
            )(params, batch, err)
        finally:
            set_global_rules(outer_rules)
        new_params, new_opt, om = adamw.adamw_update(
            opt_cfg, grads, {k: v for k, v in opt_state.items()
                             if k != "err"}, params)
        new_opt["err"] = new_err
        return new_params, new_opt, {"loss": loss, **om}

    return train_step


def train_shardings(model: Model, mesh: Mesh, shape: ShapeConfig,
                    with_err: bool = False):
    """(in_shardings, out_shardings) trees for make_train_step's fn."""
    rules = make_rules(model.cfg, mesh)
    p_axes = model.logical_axes()
    ap = model.abstract_params()
    p_sh = tree_shardings(p_axes, mesh, rules, ap)
    o_axes = adamw.opt_logical_axes(p_axes)
    o_abs = adamw.abstract_opt_state(ap)
    if with_err:
        o_axes["err"] = o_axes["master"]
        o_abs["err"] = o_abs["master"]
    opt_sh = tree_shardings(o_axes, mesh, rules, o_abs)
    b_specs, b_axes = specs_lib.train_batch_specs(model.cfg, shape,
                                                  dp=dp_size(mesh))
    b_sh = tree_shardings(b_axes, mesh, rules, b_specs)
    metric_sh = NamedSharding(mesh, P())
    in_sh = (p_sh, opt_sh, b_sh)
    out_sh = (p_sh, opt_sh,
              {"loss": metric_sh, "grad_norm": metric_sh, "lr": metric_sh})
    return in_sh, out_sh


def abstract_train_state(model: Model):
    ap = model.abstract_params()
    return ap, adamw.abstract_opt_state(ap)


# --------------------------------------------------------------------------
# Serve
# --------------------------------------------------------------------------

def make_prefill_step(model: Model, max_len: Optional[int] = None):
    def prefill_step(params, batch):
        return model.prefill(params, batch, max_len=max_len)
    return prefill_step


def make_decode_step(model: Model):
    def decode_step(params, batch, cache):
        logits, new_cache = model.decode_step(params, batch, cache)
        # greedy sampling keeps the lowered graph self-contained;
        # (B,1,V) -> (B,1), audio (B,1,C,V) -> (B,1,C)
        next_tok = jnp.argmax(logits, axis=-1)
        return next_tok.astype(jnp.int32), new_cache
    return decode_step


def serve_shardings(model: Model, mesh: Mesh, shape: ShapeConfig, *,
                    mode: str, max_len: Optional[int] = None,
                    flash_decode: bool = False):
    """Shardings for prefill ("prefill") or decode ("decode") steps."""
    cfg = model.cfg
    from repro.configs.base import padded_vocab
    rules = make_rules(cfg, mesh, flash_decode=flash_decode)
    p_sh = tree_shardings(model.logical_axes(), mesh, rules,
                          model.abstract_params())
    b_specs, b_axes = (specs_lib.prefill_batch_specs(cfg, shape)
                       if mode == "prefill"
                       else specs_lib.decode_batch_specs(cfg, shape))
    b_sh = tree_shardings(b_axes, mesh, rules, b_specs)
    c_axes = model.cache_logical_axes(max_len or shape.seq_len)
    c_abs = model.abstract_cache(shape.global_batch,
                                 max_len or shape.seq_len)
    c_sh = tree_shardings(c_axes, mesh, rules, c_abs)
    B, Vp = shape.global_batch, padded_vocab(cfg.vocab_size)
    audio = (cfg.frontend.kind == "audio"
             and cfg.frontend.num_codebooks > 1)
    C = cfg.frontend.num_codebooks
    logits_sh = sharding_for(
        ("batch", None, None, "vocab") if audio else ("batch", None, "vocab"),
        mesh, rules, shape=(B, 1, C, Vp) if audio else (B, 1, Vp))
    tok_sh = sharding_for(
        ("batch", None, None) if audio else ("batch", None), mesh, rules,
        shape=(B, 1, C) if audio else (B, 1))
    if mode == "prefill":
        return (p_sh, b_sh), (logits_sh, c_sh)
    return (p_sh, b_sh, c_sh), (tok_sh, c_sh)


# --------------------------------------------------------------------------
# Cell assembly (arch × shape -> step fn + specs + shardings)
# --------------------------------------------------------------------------

def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
               kv_layout: str = "paged", attn_impl: str = "masked",
               wkv_impl: str = "chunked", grad_compress: bool = False,
               flash_decode: bool = False,
               opt_cfg: Optional[adamw.AdamWConfig] = None):
    """Everything needed to lower one (arch × shape) cell on a mesh.

    Returns dict with: fn, example_args (ShapeDtypeStructs), in_shardings,
    out_shardings, model.
    """
    model = build_model(cfg, kv_layout=kv_layout, attn_impl=attn_impl,
                        wkv_impl=wkv_impl)
    # install activation-sharding rules for tracing (see sharding.constrain)
    set_global_rules(make_rules(cfg, mesh, flash_decode=flash_decode))
    if shape.kind == "train":
        compress = grad_compress and "pod" in mesh.axis_names
        ocfg = opt_cfg or adamw.AdamWConfig()
        fn = (make_train_step_compressed(model, ocfg, mesh) if compress
              else make_train_step(model, ocfg))
        in_sh, out_sh = train_shardings(model, mesh, shape,
                                        with_err=compress)
        b_specs, _ = specs_lib.train_batch_specs(cfg, shape,
                                                 dp=dp_size(mesh))
        ap, aopt = abstract_train_state(model)
        if compress:
            aopt["err"] = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), ap)
        args = (ap, aopt, b_specs)
        donate = (0, 1)          # params + opt state update in place
    elif shape.kind == "prefill":
        fn = make_prefill_step(model, max_len=shape.seq_len)
        in_sh, out_sh = serve_shardings(model, mesh, shape, mode="prefill",
                                        max_len=shape.seq_len)
        b_specs, _ = specs_lib.prefill_batch_specs(cfg, shape)
        args = (model.abstract_params(), b_specs)
        donate = ()
    else:  # decode
        fn = make_decode_step(model)
        in_sh, out_sh = serve_shardings(model, mesh, shape, mode="decode",
                                        max_len=shape.seq_len,
                                        flash_decode=flash_decode)
        b_specs, _ = specs_lib.decode_batch_specs(cfg, shape)
        cache = model.abstract_cache(shape.global_batch, shape.seq_len)
        args = (model.abstract_params(), b_specs, cache)
        donate = (2,)            # KV cache / recurrent state in place
    return {"fn": fn, "args": args, "in_shardings": in_sh,
            "out_shardings": out_sh, "model": model,
            "donate_argnums": donate}
