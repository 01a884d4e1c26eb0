"""How this process runs its Pallas kernels — the ONE place that decides.

Three answers, from what the process can observe:

- ``COMPILED`` (``{}``): Mosaic compiles the kernel. Every TPU backend,
  unless an environment variable below says otherwise.
- ``INTERPRET`` (``{"interpret": True}``): the Pallas interpreter, only
  under ``PS_TPU_PALLAS_INTERPRET`` (how the CPU tests exercise the kernel
  bodies) — never chosen for a TPU backend by the code itself.
- ``None``: no Pallas; the caller takes its ``jnp`` twin. Off-TPU by
  default, anywhere under ``PS_TPU_DISABLE_PALLAS``.

The mode is a dict of ``pl.pallas_call`` kwargs, and COMPILED is the EMPTY
dict, which is falsy: test the result with ``is None``, never with ``or``
(``mode or {"interpret": True}`` silently interpreted every flash kernel
on the chip for the first twenty PRs of this repo).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax

COMPILED: dict = {}
INTERPRET: dict = {"interpret": True}


def pallas_mode() -> Optional[dict]:
    """``pl.pallas_call`` kwargs for this process, or None for the jnp path."""
    if os.environ.get("PS_TPU_DISABLE_PALLAS"):
        return None
    if os.environ.get("PS_TPU_PALLAS_INTERPRET"):
        return INTERPRET
    if jax.default_backend() == "tpu":
        return COMPILED
    return None


def kernel_mode(entry: str) -> dict:
    """Mode for an entry that has NO jnp twin (the flash custom-VJP halves,
    the ring-hop partials): ``pallas_mode()``, with the interpreter standing
    in off-TPU. On a TPU the kernel is compiled or the call fails — a chip
    run never interprets unless PS_TPU_PALLAS_INTERPRET asked for it."""
    mode = pallas_mode()
    if mode is not None:
        return mode
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"PS_TPU_DISABLE_PALLAS is set but {entry} has no jnp twin; "
            f"unset it (or pick the jnp attention implementation)"
        )
    return INTERPRET


def describe(mode: Optional[dict]) -> str:
    """'compiled' | 'interpret' | 'jnp' — what validators and smoke runs
    print, derived from the mode actually used, not from the environment."""
    if mode is None:
        return "jnp"
    return "interpret" if mode.get("interpret") else "compiled"


def kernel_census(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Which path each Pallas entry took in a COMPILED program, read from
    its text (``jitted.lower(...).compile().as_text()``): ``{"mosaic":
    {kernel: n}, "jnp": {kernel: n_ops}}``. A view of the one reader of
    compiled text, obs/hlo.py (``kernel_census`` there has the rules)."""
    from ..obs.hlo import kernel_census as read

    return read(hlo_text)
