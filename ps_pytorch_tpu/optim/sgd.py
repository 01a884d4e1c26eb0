"""SGD with PyTorch update semantics, as an optax GradientTransformation.

Capability parity with the reference PS-side SGD
(/root/reference/src/optim/sgd.py:59-92), which applies — to the *already
aggregated* gradient — weight decay, heavy-ball momentum with dampening, and
optional Nesterov:

    d_p = g + weight_decay * p
    buf = d_p                                  (first step)
    buf = momentum * buf + (1-dampening) * d_p (later steps)
    d_p = d_p + momentum * buf   if nesterov else   buf
    p  -= lr * d_p

Note this is the PyTorch formulation (velocity NOT pre-multiplied by lr),
which differs from optax.sgd's trace — hence a bespoke transform. The
reference's first momentum step skips dampening (sgd.py:82-84: the buffer is
initialized to zeros then `buf.mul_(momentum).add_(d_p)`); we reproduce that
with a step counter.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Union

import chex
import jax
import jax.numpy as jnp
import optax


class SGDState(NamedTuple):
    count: chex.Array
    momentum_buffer: Optional[chex.ArrayTree]


ScalarOrSchedule = Union[float, optax.Schedule]


def _lr_at(lr: ScalarOrSchedule, count):
    return lr(count) if callable(lr) else lr


def _unwrap_vec(x):
    """(vector, rewrap) for a flat-update operand: a bare jnp vector
    passes through; a ``parallel.buckets.FlatVector`` (the PS state's
    master params/moments) contributes its padded buffer and a
    rewrap that preserves the static layout metadata."""
    from ..parallel.buckets import FlatVector  # lazy: optim stays light

    if isinstance(x, FlatVector):
        return x.flat, lambda v, _x=x: _x.replace(flat=v)
    return x, lambda v: v


def sgd(
    learning_rate: ScalarOrSchedule,
    momentum: float = 0.0,
    dampening: float = 0.0,
    weight_decay: float = 0.0,
    nesterov: bool = False,
) -> optax.GradientTransformation:
    if nesterov and (momentum <= 0 or dampening != 0):
        # parity: sgd.py:51-52
        raise ValueError("Nesterov momentum requires a momentum and zero dampening")

    def init_fn(params):
        buf = (
            jax.tree_util.tree_map(jnp.zeros_like, params) if momentum != 0 else None
        )
        return SGDState(count=jnp.zeros([], jnp.int32), momentum_buffer=buf)

    def update_fn(updates, state, params=None):
        if weight_decay != 0:
            if params is None:
                raise ValueError("weight_decay requires params")
            updates = jax.tree_util.tree_map(
                lambda g, p: g + weight_decay * p, updates, params
            )
        if momentum != 0:
            damp = jnp.where(state.count == 0, 0.0, dampening)
            buf = jax.tree_util.tree_map(
                lambda b, d: momentum * b + (1.0 - damp) * d,
                state.momentum_buffer,
                updates,
            )
            if nesterov:
                updates = jax.tree_util.tree_map(
                    lambda d, b: d + momentum * b, updates, buf
                )
            else:
                updates = buf
        else:
            buf = None
        lr = _lr_at(learning_rate, state.count)
        updates = jax.tree_util.tree_map(lambda d: -lr * d, updates)
        return updates, SGDState(count=state.count + 1, momentum_buffer=buf)

    return optax.GradientTransformation(init_fn, update_fn)


def sgd_flat(
    learning_rate: ScalarOrSchedule,
    momentum: float = 0.0,
    dampening: float = 0.0,
    weight_decay: float = 0.0,
    nesterov: bool = False,
) -> optax.GradientTransformation:
    """``sgd()`` specialized to ONE flat f32 vector — the fused update
    path for the PS trainer's flat state.

    Identical math, identical ``SGDState`` skeleton (so checkpoints are
    interchangeable with the tree transform), but weight decay, the
    momentum buffer, and Nesterov are straight whole-vector arithmetic
    with no per-leaf ``tree_map`` traversal: one elementwise chain over
    the padded flat buffer. Operands may be bare jnp vectors (the ZeRO-1
    per-shard update) or ``FlatVector``s (replicated flat state); the
    padding tail stays zero because a zero gradient produces a zero
    update (g=0, p_pad=0 => d_p=0 through every branch).

    Bit-exactness vs ``sgd()`` is pinned by
    tests/test_flat_state.py::test_flat_optimizers_bit_match_tree."""
    if nesterov and (momentum <= 0 or dampening != 0):
        raise ValueError("Nesterov momentum requires a momentum and zero dampening")

    def init_fn(params):
        v, wrap = _unwrap_vec(params)
        buf = wrap(jnp.zeros_like(v)) if momentum != 0 else None
        return SGDState(count=jnp.zeros([], jnp.int32), momentum_buffer=buf)

    def update_fn(updates, state, params=None):
        d, wrap = _unwrap_vec(updates)
        if weight_decay != 0:
            if params is None:
                raise ValueError("weight_decay requires params")
            p, _ = _unwrap_vec(params)
            d = d + weight_decay * p
        if momentum != 0:
            damp = jnp.where(state.count == 0, 0.0, dampening)
            b, _ = _unwrap_vec(state.momentum_buffer)
            buf = momentum * b + (1.0 - damp) * d
            d = d + momentum * buf if nesterov else buf
            new_buf = wrap(buf)
        else:
            new_buf = None
        lr = _lr_at(learning_rate, state.count)
        return wrap(-lr * d), SGDState(
            count=state.count + 1, momentum_buffer=new_buf
        )

    return optax.GradientTransformation(init_fn, update_fn)
