"""A GPT-2-shaped decoder (tied head, MLP of `mlp_ratio`) from `n_embd`,
`n_layer`, `vocab_size`; the traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    d, layers = int(config["n_embd"]), int(config["n_layer"])
    per_layer = (3 + 1 + 2 * int(config["mlp_ratio"])) * d * d
    return layers * per_layer + int(config["vocab_size"]) * d  # tied head


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per token. Weights: 2 ops per parameter in a product. Causal
    attention: QK^T and PV over half the square, 2*T*d per token and layer.
    Times 3 for forward and backward."""
    d, layers, t = int(config["n_embd"]), int(config["n_layer"]), int(traffic["seq_len"])
    forward = 2 * matmul_params(config) + layers * 2 * t * d
    return 3 * forward


def flash_train_step(config: dict, traffic: dict) -> dict:
    """The three flash kernels of one training step, all layers: forward 2
    products, dq 3 (scores again, dP, dQ), dkv 4 (scores again, dP, dV, dK),
    each over the causal half of the T x T square. Bytes: q, k, v, o once
    forward; q, k, v, o, do read and dq, dk, dv written backward (bf16)."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    d, layers = int(config["n_embd"]), int(config["n_layer"])
    per_product = 2 * b * t * t * d / 2          # heads x head_dim = d
    tensor = b * t * d * 2                       # one [B,T,d] bf16 array
    return {"flops": layers * 9 * per_product, "bytes": layers * 12 * tensor,
            "peak": "bf16_flops_per_s"}
