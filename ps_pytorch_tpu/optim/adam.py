"""Adam (with optional AMSGrad) with PyTorch update semantics, as an optax
GradientTransformation.

Capability parity with the reference PS-side Adam
(/root/reference/src/optim/adam.py:38-95):

    g       = g + weight_decay * p
    m       = beta1 * m + (1-beta1) * g
    v       = beta2 * v + (1-beta2) * g^2
    v_hat   = max(v_hat, v)              (amsgrad only; denom uses v_hat)
    denom   = sqrt(v or v_hat) + eps     (NB: eps added AFTER sqrt, and the
                                          bias correction multiplies the step
                                          size, not the moments — both match
                                          torch, and differ from optax.adam)
    step_sz = lr * sqrt(1-beta2^t) / (1-beta1^t)
    p      -= step_sz * m / denom
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import chex
import jax
import jax.numpy as jnp
import optax

from .sgd import ScalarOrSchedule, _lr_at, _unwrap_vec


class AdamState(NamedTuple):
    count: chex.Array
    exp_avg: chex.ArrayTree
    exp_avg_sq: chex.ArrayTree
    max_exp_avg_sq: Optional[chex.ArrayTree]


def adam(
    learning_rate: ScalarOrSchedule = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    amsgrad: bool = False,
) -> optax.GradientTransformation:
    def init_fn(params):
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
        return AdamState(
            count=jnp.zeros([], jnp.int32),
            exp_avg=zeros(),
            exp_avg_sq=zeros(),
            max_exp_avg_sq=zeros() if amsgrad else None,
        )

    def update_fn(updates, state, params=None):
        if weight_decay != 0:
            if params is None:
                raise ValueError("weight_decay requires params")
            updates = jax.tree_util.tree_map(
                lambda g, p: g + weight_decay * p, updates, params
            )
        count = state.count + 1
        m = jax.tree_util.tree_map(
            lambda m_, g: b1 * m_ + (1 - b1) * g, state.exp_avg, updates
        )
        v = jax.tree_util.tree_map(
            lambda v_, g: b2 * v_ + (1 - b2) * g * g, state.exp_avg_sq, updates
        )
        if amsgrad:
            vmax = jax.tree_util.tree_map(jnp.maximum, state.max_exp_avg_sq, v)
            denom_tree = vmax
        else:
            vmax = None
            denom_tree = v
        c = count.astype(jnp.float32)
        bias1 = 1 - b1**c
        bias2 = 1 - b2**c
        step_size = _lr_at(learning_rate, state.count) * jnp.sqrt(bias2) / bias1
        new_updates = jax.tree_util.tree_map(
            lambda m_, d: -step_size * m_ / (jnp.sqrt(d) + eps), m, denom_tree
        )
        return new_updates, AdamState(
            count=count, exp_avg=m, exp_avg_sq=v, max_exp_avg_sq=vmax
        )

    return optax.GradientTransformation(init_fn, update_fn)


def adam_flat(
    learning_rate: ScalarOrSchedule = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    amsgrad: bool = False,
) -> optax.GradientTransformation:
    """``adam()`` specialized to ONE flat f32 vector — the fused update
    path for the PS trainer's flat state (see optim/sgd.sgd_flat).

    Same math, same ``AdamState`` skeleton; both moments (and the
    AMSGrad max) are whole vectors, so the entire update is one fused
    elementwise chain instead of a ``tree_map`` per leaf. The padding
    tail stays zero: g=0 keeps m=v=0 and the update term is
    ``-step * 0 / (sqrt(0) + eps) = 0``."""

    def init_fn(params):
        v, wrap = _unwrap_vec(params)
        zeros = lambda: wrap(jnp.zeros_like(v))
        return AdamState(
            count=jnp.zeros([], jnp.int32),
            exp_avg=zeros(),
            exp_avg_sq=zeros(),
            max_exp_avg_sq=zeros() if amsgrad else None,
        )

    def update_fn(updates, state, params=None):
        g, wrap = _unwrap_vec(updates)
        if weight_decay != 0:
            if params is None:
                raise ValueError("weight_decay requires params")
            p, _ = _unwrap_vec(params)
            g = g + weight_decay * p
        count = state.count + 1
        m_prev, _ = _unwrap_vec(state.exp_avg)
        v_prev, _ = _unwrap_vec(state.exp_avg_sq)
        m = b1 * m_prev + (1 - b1) * g
        v = b2 * v_prev + (1 - b2) * g * g
        if amsgrad:
            vmax_prev, _ = _unwrap_vec(state.max_exp_avg_sq)
            vmax = jnp.maximum(vmax_prev, v)
            denom = vmax
            new_vmax = wrap(vmax)
        else:
            denom = v
            new_vmax = None
        c = count.astype(jnp.float32)
        bias1 = 1 - b1**c
        bias2 = 1 - b2**c
        step_size = _lr_at(learning_rate, state.count) * jnp.sqrt(bias2) / bias1
        new_updates = -step_size * m / (jnp.sqrt(denom) + eps)
        return wrap(new_updates), AdamState(
            count=count, exp_avg=wrap(m), exp_avg_sq=wrap(v),
            max_exp_avg_sq=new_vmax,
        )

    return optax.GradientTransformation(init_fn, update_fn)
