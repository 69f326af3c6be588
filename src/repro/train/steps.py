"""Jit-able step factories.

``make_train_step`` builds the canonical fused step: loss -> grads ->
global-norm clip -> AdamW.  Data parallelism is expressed purely
through batch sharding (pjit inserts the gradient reduce); with
``compress_pod_grads=True`` the cross-pod leg of that reduction is
replaced by an int8 error-feedback all-reduce inside a partial-manual
``jax.shard_map`` over the 'pod' axis, leaving the intra-pod axes in
auto (pjit) mode -- hierarchical reduction, the multi-pod
distributed-optimization trick.

``make_serve_steps`` builds (prefill_step, decode_one) for serving.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import loss_fn, prefill, decode_step
from repro.models.config import ModelConfig
from repro.parallel import compression
from repro.train.optimizer import (AdamWState, adamw_init, adamw_update,
                                   clip_by_global_norm)


def _partial_shard_map(f, mesh, manual_axes, in_specs, out_specs):
    """Partial-manual shard_map: ``manual_axes`` are manual, every
    other mesh axis stays in auto (pjit) mode."""
    return jax.shard_map(f, mesh=mesh, axis_names=frozenset(manual_axes),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


class TrainState(NamedTuple):
    params: object
    opt: AdamWState
    ef: Optional[object] = None      # error-feedback residuals


def init_train_state(cfg: ModelConfig, key, bits8: bool = False,
                     error_feedback: bool = False) -> TrainState:
    from repro.models import init_params
    params = init_params(cfg, key)
    opt = adamw_init(params, bits8=bits8)
    ef = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
          if error_feedback else None)
    return TrainState(params, opt, ef)


def make_train_step(cfg: ModelConfig, lr_fn: Callable,
                    max_grad_norm: float = 1.0, bits8: bool = False,
                    compress_pod_grads: bool = False, mesh=None):
    """Returns step(state, batch) -> (state, metrics)."""

    def base_step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch))(state.params)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = lr_fn(state.opt.step)
        params, opt = adamw_update(grads, state.opt, state.params, lr,
                                   bits8=bits8)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return TrainState(params, opt, state.ef), metrics

    if not compress_pod_grads:
        return base_step

    assert mesh is not None and "pod" in mesh.shape, \
        "compressed pod reduction needs a 'pod' mesh axis"

    def compressed_step(state: TrainState, batch):
        # grads on the pod-local batch shard; 'data'/'model' stay auto.
        def local_grads(params, batch):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch))(params)
            return loss, grads

        def podwise(params, ef, batch):
            # inside the manual 'pod' region the model's sharding
            # constraints must not reference 'pod' (Manual axes cannot
            # mix with Auto in a PartitionSpec) -- activate pod-less
            # rules for the trace of the loss/grad computation.
            from repro.parallel import sharding as shardlib
            with shardlib.activate(None):   # let SPMD auto-shard inside
                loss, grads = local_grads(params, batch)
            flat_g, tdef = jax.tree.flatten(grads)
            flat_e = jax.tree.flatten(ef)[0]
            out_g, out_e = [], []
            for g, e in zip(flat_g, flat_e):
                rg, re = compression.compressed_psum(g, "pod", e)
                out_g.append(rg.astype(g.dtype))
                out_e.append(re)
            loss = jax.lax.pmean(loss, "pod")
            return loss, tdef.unflatten(out_g), tdef.unflatten(out_e)

        shmapped = _partial_shard_map(
            podwise, mesh, {"pod"},
            in_specs=(P(), P(), P("pod")),
            out_specs=(P(), P(), P()))
        loss, grads, ef = shmapped(state.params, state.ef, batch)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = lr_fn(state.opt.step)
        params, opt = adamw_update(grads, state.opt, state.params, lr,
                                   bits8=bits8)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return TrainState(params, opt, ef), metrics

    return compressed_step


def make_serve_steps(cfg: ModelConfig, s_max: int):
    """(prefill_step, decode_one).  decode_one greedily samples."""

    def prefill_step(params, batch):
        logits, cache = prefill(params, cfg, batch, s_max=s_max)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    def decode_one(params, tokens, cache, pos):
        logits, cache = decode_step(params, cfg, tokens, cache, pos)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step, decode_one
