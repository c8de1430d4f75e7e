"""Adam/AdamW in pure JAX, with LR schedules and global-norm clipping.

State is a pytree mirror of the params (``m``/``v`` in fp32 regardless of
param dtype — bf16 moments diverge), plus a scalar step. ZeRO-1 sharding
of the moments is applied by the launcher via sharding constraints
(dist/sharding.py::zero1_spec); this module is distribution-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 2e-4  # paper's classification default
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0  # >0 -> AdamW (decoupled)
    clip_norm: float = 0.0  # 0 disables
    schedule: str = "constant"  # constant | cosine | warmup_cosine
    warmup_steps: int = 0
    total_steps: int = 0
    min_lr_frac: float = 0.1


def lr_at(cfg: AdamConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    lr = jnp.float32(cfg.lr)
    if cfg.schedule == "constant":
        return lr
    total = max(cfg.total_steps, 1)
    if cfg.schedule in ("cosine", "warmup_cosine"):
        warm = cfg.warmup_steps if cfg.schedule == "warmup_cosine" else 0
        warm_lr = lr * jnp.clip(s / max(warm, 1), 0.0, 1.0) if warm else lr
        prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
        cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(s < warm, warm_lr, lr * cos)
    raise ValueError(f"unknown schedule {cfg.schedule}")


def init(params) -> AdamState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
    )


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), gn


def apply_updates(
    cfg: AdamConfig, params, grads, state: AdamState
) -> tuple[Any, AdamState, dict]:
    """One Adam(W) step. Returns (new_params, new_state, metrics).

    Runs under the name scope ``adam``, so its operations carry it in
    their HLO ``op_name`` and a device trace can tell the update apart
    from the forward and backward.
    """
    with jax.named_scope("adam"):
        gn = global_norm(grads)
        if cfg.clip_norm > 0:
            grads, _ = clip_by_global_norm(grads, cfg.clip_norm)
        step = state.step + 1
        lr = lr_at(cfg, step)
        b1, b2 = cfg.b1, cfg.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m_n = b1 * m + (1 - b1) * g32
            v_n = b2 * v + (1 - b2) * jnp.square(g32)
            mhat = m_n / bc1
            vhat = v_n / bc2
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if cfg.weight_decay > 0:
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            p_n = p.astype(jnp.float32) - lr * delta
            return p_n.astype(p.dtype), m_n, v_n

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(state.m)
        flat_v = jax.tree.leaves(state.v)
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v, strict=True)]
        new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
        new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
        new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
        return new_p, AdamState(step, new_m, new_v), {"grad_norm": gn, "lr": lr}


def adamw(cfg: AdamConfig | None = None) -> AdamConfig:
    """The paper's generation-task optimizer (AdamW, default params)."""
    return cfg or AdamConfig(lr=1e-3, weight_decay=1e-2)
