"""DFedAvgM — Decentralized Federated Averaging with Momentum (paper eq. 2.1).

Per communication round t, client i runs K local heavy-ball steps

    w^{t,k+1} = w^{t,k} - eta_t * grad f_i(w^{t,k}; xi) + beta (w^{t,k} - w^{t,k-1})

with w^{t,-1} = w^{t,0} (momentum resets at each round boundary — paper
convention), then gossips: w_i^{t+1,0} = sum_l m_il w_l^{t,K}.

This module is executor-agnostic: the same `local_round` runs

* stacked under `jax.vmap` for the N-client simulator (benchmarks mirror the
  paper's experiments), and
* per-shard inside `shard_map` for the production multi-pod trainer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = [
    "DFedAvgMConfig",
    "momentum_update",
    "local_round",
    "make_client_round",
]

PyTree = Any
LossFn = Callable[[PyTree, Any], tuple[jax.Array, Any]]  # (params, batch) -> (loss, aux)


@dataclasses.dataclass(frozen=True)
class DFedAvgMConfig:
    """Hyper-parameters of the local solver (paper eq. 2.1)."""

    local_steps: int = 3          # K
    lr: float = 0.01              # eta (constant; schedules applied by caller)
    momentum: float = 0.9         # beta
    reset_momentum: bool = True   # w^{t,-1} = w^{t,0} (paper-faithful)
    grad_clip: float | None = None
    weight_decay: float = 0.0
    grad_accum: int = 1           # microbatches per local step (memory knob)
    # dtype of the microbatch-gradient accumulator; param dtype keeps the
    # per-microbatch reduce traffic in bf16 (f32 doubles collective bytes)
    accum_dtype: str | None = None


def _clip(grads: PyTree, max_norm: float) -> PyTree:
    g2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    norm = jnp.sqrt(g2)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def momentum_update(params: PyTree, velocity: PyTree, grads: PyTree,
                    lr, beta) -> tuple[PyTree, PyTree]:
    """Heavy-ball: v' = beta v - lr g ; w' = w + v'  (== paper eq. 2.1)."""
    new_v = jax.tree.map(
        lambda v, g: (beta * v.astype(jnp.float32)
                      - lr * g.astype(jnp.float32)).astype(v.dtype),
        velocity, grads)
    new_p = jax.tree.map(lambda p, v: (p.astype(jnp.float32)
                                       + v.astype(jnp.float32)).astype(p.dtype),
                         params, new_v)
    return new_p, new_v


def local_round(
    params: PyTree,
    velocity: PyTree,
    batches: PyTree,
    loss_fn: LossFn,
    cfg: DFedAvgMConfig,
    lr: jax.Array | float | None = None,
    update_fn: Callable[..., tuple[PyTree, PyTree]] | None = None,
) -> tuple[PyTree, PyTree, jax.Array]:
    """K local momentum steps for ONE client.

    Args:
      params/velocity: this client's model state.
      batches: pytree whose leaves have leading axis K (one slice per local step).
      loss_fn: (params, batch) -> (loss, aux).
      lr: overrides cfg.lr (e.g. a per-round scheduled value).
      update_fn: optional fused (params, velocity, grads, lr, beta) updater
        (the Pallas kernel on TPU); defaults to `momentum_update`.

    Returns (params, velocity, mean_loss).
    """
    with jax.named_scope("dfl.local"):
        lr = cfg.lr if lr is None else lr
        upd = update_fn or momentum_update
        if cfg.reset_momentum:
            velocity = jax.tree.map(jnp.zeros_like, velocity)

        def grads_of(p, batch):
            if cfg.grad_accum <= 1:
                return jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
            # gradient accumulation: scan over microbatches, average grads —
            # bounds transient activation memory for the giant MoE shapes
            mb = jax.tree.map(
                lambda x: x.reshape((cfg.grad_accum, x.shape[0] // cfg.grad_accum)
                                    + x.shape[1:]), batch)

            adt = cfg.accum_dtype

            def acc(carry, b):
                (loss, _aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
                gsum, lsum = carry
                return (jax.tree.map(lambda a, x: a + x.astype(a.dtype), gsum, g),
                        lsum + loss), None

            zeros = jax.tree.map(
                lambda w: jnp.zeros(w.shape, jnp.dtype(adt) if adt else w.dtype), p)
            (gsum, lsum), _ = jax.lax.scan(
                acc, (zeros, jnp.zeros((), jnp.float32)), mb)
            inv = 1.0 / cfg.grad_accum
            return ((lsum * inv, None),
                    jax.tree.map(lambda g, w: (g * inv).astype(w.dtype), gsum, p))

        def step(carry, batch):
            p, v = carry
            (loss, _aux), grads = grads_of(p, batch)
            if cfg.grad_clip is not None:
                grads = _clip(grads, cfg.grad_clip)
            if cfg.weight_decay:
                grads = jax.tree.map(lambda g, w: g + cfg.weight_decay * w, grads, p)
            p, v = upd(p, v, grads, lr, cfg.momentum)
            return (p, v), loss

        (params, velocity), losses = jax.lax.scan(step, (params, velocity), batches)
        return params, velocity, jnp.mean(losses)


def make_client_round(loss_fn: LossFn, cfg: DFedAvgMConfig,
                      update_fn=None) -> Callable:
    """vmap-able per-client round: (params, velocity, batches[, lr]) -> ..."""

    def fn(params, velocity, batches, lr=None):
        return local_round(params, velocity, batches, loss_fn, cfg, lr=lr,
                           update_fn=update_fn)

    return fn
