"""Optimizers with PyTorch update semantics (reference: src/optim/).

`build_optimizer` mirrors the reference's optimizer wiring: the PS constructs
`SGD(model.parameters(), lr, momentum)` (sync_replicas_master_nn.py:122-123)
and workers use torch.optim.SGD (distributed_worker.py:97); Adam/AMSGrad is the
in-tree alternative (src/optim/adam.py).
"""

from __future__ import annotations

import optax

from .adam import AdamState, adam, adam_flat
from .sgd import SGDState, sgd, sgd_flat

OPTIMIZER_REGISTRY = ("sgd", "adam", "amsgrad")


def build_optimizer(
    name: str,
    learning_rate,
    momentum: float = 0.9,
    dampening: float = 0.0,
    weight_decay: float = 0.0,
    nesterov: bool = False,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    flat: bool = False,
) -> optax.GradientTransformation:
    """``flat=True`` returns the whole-vector variant (sgd_flat/adam_flat)
    the PS trainer's flat state takes — bit-identical math on the
    padded flat state, no per-leaf tree_map. The tree transforms also
    ACCEPT flat operands (a tree_map over one vector leaf is one vector
    op), so flat is an explicitness/efficiency choice, not a correctness
    requirement."""
    name = name.lower()
    if name == "sgd":
        make = sgd_flat if flat else sgd
        return make(
            learning_rate,
            momentum=momentum,
            dampening=dampening,
            weight_decay=weight_decay,
            nesterov=nesterov,
        )
    if name in ("adam", "amsgrad"):
        make = adam_flat if flat else adam
        return make(
            learning_rate,
            b1=b1,
            b2=b2,
            eps=eps,
            weight_decay=weight_decay,
            amsgrad=(name == "amsgrad"),
        )
    raise ValueError(f"unknown optimizer {name!r}; choose from {OPTIMIZER_REGISTRY}")
