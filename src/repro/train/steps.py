"""Jittable train / prefill / decode steps.

train_step: value_and_grad over the model loss with mixed precision
(fp32 master weights cast to bf16 for fwd/bwd), optional microbatch
gradient accumulation (a lax.scan over microbatches — the standard
memory/throughput knob), AdamW update.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.common import cast_tree
from repro.models.model_zoo import Model
from repro.train.optimizer import AdamWConfig, apply_updates, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: AdamWConfig = AdamWConfig()
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: Optional[str] = None  # None | "dots"
    microbatches: int = 1  # gradient-accumulation factor


def make_train_step(model: Model, tcfg: TrainConfig):
    def loss_of(params, batch):
        p = cast_tree(params, tcfg.compute_dtype)
        # Force the bf16 working copy to materialize ONCE per step: without
        # the barrier XLA sinks the convert into the layer scan, and every
        # layer iteration re-reads the full fp32 parameter stack (measured
        # 59.5 GB/iteration on qwen3-moe — EXPERIMENTS.md §Perf iter 2).
        p = jax.lax.optimization_barrier(p)
        b = dict(batch)
        if "embeds" in b:
            b["embeds"] = b["embeds"].astype(tcfg.compute_dtype)
        loss, metrics = model.loss_fn(
            p, b, remat=tcfg.remat, remat_policy=tcfg.remat_policy
        )
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def train_step(params, opt_state, batch):
        if tcfg.microbatches > 1:
            n = tcfg.microbatches
            split = jax.tree.map(
                lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch
            )

            def mb(carry, b):
                acc, lsum = carry
                (loss, _), g = grad_fn(params, b)
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, lsum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum), _ = jax.lax.scan(mb, (zeros, 0.0), split)
            grads = jax.tree.map(lambda g: g / n, gsum)
            loss = lsum / n
            metrics = {}
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        params, opt_state, opt_metrics = apply_updates(
            tcfg.opt, params, grads, opt_state
        )
        return params, opt_state, {"loss": loss, **metrics, **opt_metrics}

    return train_step


def make_serve_steps(model: Model):
    """Returns (prefill_step, decode_step) for batched serving."""

    def prefill_step(params, batch, max_len: int):
        p = cast_tree(params, jnp.bfloat16)
        return model.prefill_fn(p, batch, max_len)

    def decode_step(params, state, tokens, cache_len):
        p = cast_tree(params, jnp.bfloat16)
        logits, state = model.decode_fn(p, state, tokens, cache_len)
        return logits, state, cache_len + 1

    return prefill_step, decode_step


def init_train_state(model: Model, key, dtype=jnp.float32):
    params = model.init_params(key, dtype)
    return params, init_opt_state(params)
