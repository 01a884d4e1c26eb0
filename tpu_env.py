"""The CPU-mesh environment the tests and the tracing tools run in.

Tests, the contract checker and the dry runs drive the multi-device
schemes on N *virtual* CPU devices. jax reads the platform and the device
count once, when the backend is created, so a process that needs them must
have them in its environment before it imports jax: either it sets them
first thing (conftest.py) or it re-executes itself under them (the CLIs
that may be started from any shell). The rule lives here only, and this
module imports nothing but the standard library, so importing it can never
touch jax. The chip is not this module's business — `chip_smoke.py` is the
program that uses it.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def clean_cpu_env(n_devices: int | None = None) -> dict:
    """Environment for a CPU-only jax process with virtual devices.

    n_devices=None keeps an existing device-count flag (defaulting to 8 if
    absent — the test mesh); an int forces exactly that many virtual
    devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if n_devices is None:
        if _COUNT_FLAG not in flags:
            flags += f" {_COUNT_FLAG}=8"
    else:
        flags = re.sub(_COUNT_FLAG + r"=\d+", "", flags)
        flags += f" {_COUNT_FLAG}={n_devices}"
    env["XLA_FLAGS"] = flags.strip()
    return env


def env_is_clean(n_devices: int | None = None) -> bool:
    """True when the CURRENT process already has that environment (so jax
    may be imported in-process and will see the CPU mesh)."""
    if os.environ.get("JAX_PLATFORMS", "cpu") != "cpu":
        return False
    if n_devices is not None and not re.search(
        # anchored: count=8 must not match count=80
        rf"{_COUNT_FLAG}={n_devices}(?!\d)", os.environ.get("XLA_FLAGS", "")
    ):
        return False
    return True
