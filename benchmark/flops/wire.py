"""The gradient wire's kernels, for any configuration that states its
`parameters`."""

from __future__ import annotations


def ps_quantize_step(config: dict, traffic: dict) -> dict:
    """Quantising one worker's gradient for the int8 wire: every parameter
    read once as float32 and written once as int8."""
    p = int(config["parameters"])
    return {"flops": 0.0, "bytes": 5 * p, "peak": "bf16_flops_per_s"}
