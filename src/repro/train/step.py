"""Distributed train step builder.

The paper's training contract — partition-local compute, explicit global
combine — appears here at pod scale: the batch shards over ("pod","data"),
parameters FSDP-shard over "data" and tensor-shard over "model", and the
gradient combine is whatever GSPMD lowers for those shardings
(reduce-scatter + all-gather; the §Perf log hillclimbs it).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.transformer import TransformerLM, init_model
from repro.optim.optimizers import OptimizerDef, adamw
from repro.sharding.rules import DEFAULT_RULES, ShardingRules, logical_to_spec, shardings_for
from repro.train.loss import (chunked_cross_entropy_from_hidden,
                              cross_entropy_loss)

__all__ = ["TrainState", "init_train_state", "make_train_step", "batch_specs"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray


def init_train_state(key: jax.Array, cfg: ArchConfig,
                     optimizer: Optional[OptimizerDef] = None
                     ) -> Tuple[TrainState, Any]:
    """Returns (state, axes) — axes is the logical-axis tree for params."""
    optimizer = optimizer or adamw()
    params, axes = init_model(key, cfg)
    opt_state = optimizer.init(params)
    return TrainState(params=params, opt_state=opt_state,
                      step=jnp.zeros((), jnp.int32)), axes


def state_shardings(state: TrainState, axes: Any, mesh: Mesh,
                    rules: ShardingRules = DEFAULT_RULES) -> TrainState:
    """Shardings for the full TrainState: optimizer moments mirror params."""
    p_sh = shardings_for(axes, state.params, mesh, rules)

    def opt_sh(entry):
        # every optimizer-state subtree mirrors the param tree structure
        return jax.tree.map(lambda _, s: s, entry, p_sh) if entry else entry

    o_sh = {k: jax.tree.map(lambda _, s: s, v, p_sh)
            for k, v in state.opt_state.items()}
    return TrainState(params=p_sh, opt_state=o_sh,
                      step=NamedSharding(mesh, P()))


#: logical axes for each possible batch entry (mapped per-mesh by
#: sharding.rules.logical_to_spec)
BATCH_AXES: Dict[str, Tuple] = {
    "tokens": ("batch", None),
    "labels": ("batch", None),
    "vision_embeds": ("batch", None, None),
    "encoder_frames": ("batch", None, None),
}


def batch_specs(batch: Dict[str, Any], mesh: Mesh,
                rules: ShardingRules = DEFAULT_RULES) -> Dict[str, NamedSharding]:
    return {
        k: NamedSharding(mesh, logical_to_spec(BATCH_AXES[k], tuple(v.shape),
                                               mesh, rules))
        for k, v in batch.items()
    }


def make_train_step(cfg: ArchConfig, optimizer: Optional[OptimizerDef] = None,
                    mesh: Optional[Mesh] = None,
                    rules: ShardingRules = DEFAULT_RULES,
                    donate: bool = True,
                    grad_accum: int = 1) -> Callable:
    """Returns jitted ``step(state, batch) -> (state, metrics)``.

    batch: {"tokens": (B,S) int32, "labels": (B,S) int32,
            optional "vision_embeds": (B,Tv,D), "encoder_frames": (B,Se,D)}

    ``grad_accum > 1`` splits the batch into that many microbatches and
    accumulates gradients through a lax.scan before the single optimizer
    update — same math as the full batch (mean-of-means over equal-sized
    microbatches), 1/k the activation footprint.
    """
    optimizer = optimizer or adamw()
    model = TransformerLM(cfg)

    def loss_fn(params, batch):
        if cfg.loss_vocab_chunk:
            # §Perf chunked-xent path: LM head fused into the loss, the
            # (tokens, V) logits tensor is never materialized.
            hidden, aux = model.forward_hidden(
                params, batch["tokens"],
                vision_embeds=batch.get("vision_embeds"),
                encoder_frames=batch.get("encoder_frames"))
            if cfg.vision_tokens:
                hidden = hidden[:, cfg.vision_tokens:]
            B, S, D = hidden.shape
            if cfg.logits_scaling != 1.0:    # logits / s == (hidden / s) · table
                hidden = hidden / cfg.logits_scaling
            table = params["embed"]["head"].T if "head" in params["embed"] \
                else params["embed"]["tok"]
            loss = chunked_cross_entropy_from_hidden(
                hidden[:, :-1].reshape(B * (S - 1), D), table,
                batch["labels"][:, 1:].reshape(B * (S - 1)),
                chunk=cfg.loss_vocab_chunk)
        else:
            logits, aux = model.forward(
                params, batch["tokens"],
                vision_embeds=batch.get("vision_embeds"),
                encoder_frames=batch.get("encoder_frames"))
            # logits cover [vision_tokens + text]; labels align with text tail
            if cfg.vision_tokens:
                logits = logits[:, cfg.vision_tokens:]
            loss = cross_entropy_loss(logits[:, :-1], batch["labels"][:, 1:])
        total = loss + cfg.router_aux_weight * aux
        return total, {"loss": loss, "aux": aux}

    def grads_of(params, batch):
        if grad_accum <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        B = batch["tokens"].shape[0]
        if B % grad_accum:
            raise ValueError(f"batch {B} not divisible by grad_accum {grad_accum}")
        micro = {k: v.reshape((grad_accum, B // grad_accum) + v.shape[1:])
                 for k, v in batch.items()}

        def body(acc, mb):
            (t, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            acc_g, acc_t, acc_m = acc
            acc_g = jax.tree.map(jnp.add, acc_g, g)
            return (acc_g, acc_t + t, jax.tree.map(jnp.add, acc_m, m)), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g_sum, t_sum, m_sum), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32),
                   {"loss": jnp.zeros((), jnp.float32),
                    "aux": jnp.zeros((), jnp.float32)}), micro)
        k = float(grad_accum)
        grads = jax.tree.map(lambda g: (g / k).astype(jnp.float32), g_sum)
        return (t_sum / k, jax.tree.map(lambda x: x / k, m_sum)), grads

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        if mesh is not None:
            spec = logical_to_spec(("batch", None), batch["tokens"].shape, mesh, rules)
            batch = dict(batch)
            batch["tokens"] = jax.lax.with_sharding_constraint(
                batch["tokens"], NamedSharding(mesh, spec))
        (total, metrics), grads = grads_of(state.params, batch)
        new_params, new_opt = optimizer.update(grads, state.opt_state,
                                               state.params, state.step)
        metrics = dict(metrics)
        metrics["total_loss"] = total
        metrics["grad_norm"] = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        return TrainState(params=new_params, opt_state=new_opt,
                          step=state.step + 1), metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    return step_fn  # caller jits with explicit in/out shardings (launch.dryrun)
