"""Device-side non-finite gradient guard state + helpers.

The guard lives INSIDE the jitted PS train step (parallel/ps.py): each
worker reduces its gradient leaves to one all-finite flag, a single
int32 ``lax.pmin`` agrees on it mesh-wide (4 bytes on the wire, no host
transfer), and the whole state update is selected against the flag —
a bad step applies the identity instead of the optimizer. The state is
flat, so that rollback is a ``jnp.where`` over a handful of whole
vectors (params + each optimizer moment ride as single padded buffers),
not one select per pytree leaf. Counters are
carried in ``GuardState`` (part of PSTrainState, so they checkpoint and
resume) and surfaced through the metrics dict the host already fetches
once per log window, so a healthy run pays zero extra host syncs.

Dynamic loss scaling (``PSConfig.dynamic_loss_scale``) rides the same
state: the loss is multiplied by ``scale`` before backprop and the
gradients divided by it after, the scale backs off 2x on every skipped
(overflowed) step and grows 2x after ``loss_scale_growth_interval``
consecutive good steps — the standard AMP recipe, aimed here at the int8
compression schemes whose wire range is the tightest.
"""

from __future__ import annotations

from typing import Any

import flax.struct
import jax
import jax.numpy as jnp

# dynamic loss scale bounds: backing off below 1.0 would silently shrink
# healthy gradients; growing past 2^24 adds nothing once f32 headroom is
# exhausted
MIN_LOSS_SCALE = 1.0
MAX_LOSS_SCALE = float(2 ** 24)


@flax.struct.dataclass
class GuardState:
    """Per-run guard counters, replicated on the mesh and checkpointed.

    ``skipped``: total steps skipped (non-finite gradients somewhere on
    the mesh); ``consec``: current skip streak (the host aborts when it
    crosses TrainConfig.max_consecutive_skips); ``good``: current streak
    of finite steps (drives loss-scale growth); ``scale``: the live loss
    scale (1.0 when dynamic scaling is off); ``dyn``: 1 iff dynamic loss
    scaling was ON when this state was produced — checkpoint restore
    needs it to tell a dynamic-off scale of 1.0 apart from a dynamic run
    that legitimately backed off to MIN_LOSS_SCALE (both store 1.0, but
    only the former should re-init to loss_scale_init on a
    --dynamic-loss-scale resume)."""

    skipped: jax.Array
    consec: jax.Array
    good: jax.Array
    scale: jax.Array
    dyn: jax.Array


def init_guard_state(
    loss_scale: float = 1.0, dynamic: bool = False
) -> GuardState:
    return GuardState(
        skipped=jnp.zeros([], jnp.int32),
        consec=jnp.zeros([], jnp.int32),
        good=jnp.zeros([], jnp.int32),
        scale=jnp.asarray(loss_scale, jnp.float32),
        dyn=jnp.asarray(int(dynamic), jnp.int32),
    )


def reconcile_guard_state(stored: dict, fresh: dict) -> dict:
    """Merge a checkpointed guard-state dict into the current config's
    fresh one (both flax state-dicts); checkpoint.py calls this for the
    resettable ``guard_state`` field so the persistence layer stays
    ignorant of GuardState's field names and migration rules.

    Stored counters win — but the live loss scale is MATH once dynamic
    scaling is on: a dynamic-OFF checkpoint (dyn flag 0) resumed with
    --dynamic-loss-scale must start from the target's init instead of
    regrowing from 1.0 over ~growth_interval*log2(init) steps. The dyn
    flag (not scale==1.0) decides, so a dynamic run that legitimately
    backed off to MIN_LOSS_SCALE keeps its 1.0. The flag itself always
    reflects the CURRENT config."""
    sd, td = stored.get("dyn"), fresh.get("dyn")
    if sd is not None and td is not None:
        if int(td) == 1 and int(sd) == 0:
            stored["scale"] = fresh.get("scale")
        stored["dyn"] = td
    return stored


def tree_all_finite(tree: Any) -> jax.Array:
    """Scalar bool: every element of every leaf is finite (no NaN/Inf).

    One fused reduction per leaf; the cross-leaf AND is a handful of
    scalar ops — noise next to the backward pass it guards."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.asarray(True)
    flags = [jnp.all(jnp.isfinite(l)) for l in leaves]
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def update_guard_state(
    g: GuardState,
    finite: jax.Array,
    dynamic_loss_scale: bool,
    growth_interval: int,
) -> GuardState:
    """Pure device-side counter/scale update for one step.

    grow-on-success / back-off-on-overflow: a skipped step halves the
    scale (floored at MIN_LOSS_SCALE); ``growth_interval`` consecutive
    good steps double it (capped at MAX_LOSS_SCALE) and restart the good
    streak."""
    bad = (~finite).astype(jnp.int32)
    good1 = jnp.where(finite, g.good + 1, 0)
    if dynamic_loss_scale:
        do_grow = jnp.logical_and(finite, good1 >= growth_interval)
        grown = jnp.where(
            do_grow, jnp.minimum(g.scale * 2.0, MAX_LOSS_SCALE), g.scale
        )
        scale = jnp.where(
            finite, grown, jnp.maximum(g.scale * 0.5, MIN_LOSS_SCALE)
        )
        good1 = jnp.where(do_grow, 0, good1)
    else:
        scale = g.scale
    return GuardState(
        skipped=g.skipped + bad,
        consec=jnp.where(finite, 0, g.consec + 1),
        good=good1,
        scale=scale,
        dyn=g.dyn,
    )
