"""A `granitemoehybrid`-shaped decoder without routed experts (Mamba-2
state-space layers and grouped-query attention layers in the order
`layer_types` gives, each followed by a gated MLP `shared_intermediate_size`
wide, tied head) from the published keys; `vocab_size` is the slice held.
The traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations


def _mamba(config: dict):
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, n = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    return h, p, g, n, int(config["mamba_chunk_size"])


def _layers(config: dict):
    kinds = list(config["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def mamba_matmul_params(config: dict) -> int:
    """in_proj [d, z | xBC | dt] and out_proj; the conv's taps, the decay
    parameters and the norms are in no product."""
    d = int(config["hidden_size"])
    h, p, g, n, _ = _mamba(config)
    inner = h * p
    return d * (inner + inner + 2 * g * n + h) + inner * d


def attention_matmul_params(config: dict) -> int:
    d, hq, hkv = (int(config[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads"))
    hd = d // hq
    return 2 * d * hq * hd + 2 * d * hkv * hd


def matmul_params(config: dict) -> int:
    """Parameters in a product for one token, over all layers held, and the
    tied head (the embedding is a lookup)."""
    d, f = int(config["hidden_size"]), int(config["shared_intermediate_size"])
    mamba, attention = _layers(config)
    return (mamba * mamba_matmul_params(config) + attention * attention_matmul_params(config)
            + (mamba + attention) * 3 * d * f + d * int(config["vocab_size"]))


def scan_flops_per_token(config: dict) -> int:
    """The chunked scan's products for one token of one state-space layer,
    forward: C B^T over the chunk's square once a group (2 * Q * N), the
    masked product (L o C B^T)(dt x) per head (2 * Q * P), the chunk's state
    made from the token and read by it, per head (2 * 2 * P * N). The whole
    chunk square is counted: that is what the products compute."""
    h, p, g, n, q = _mamba(config)
    return g * 2 * q * n + h * 2 * q * p + h * 2 * 2 * p * n


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per token. Weights: 2 ops per parameter in a product. The scan's
    products in each state-space layer. Causal attention in each attention
    layer: QK^T and PV over half the square, 2 * (T/2) * heads * 2 *
    head_dim. Times 3 for forward and backward."""
    d, hq = int(config["hidden_size"]), int(config["num_attention_heads"])
    t = int(traffic["seq_len"])
    mamba, attention = _layers(config)
    forward = (2 * matmul_params(config) + mamba * scan_flops_per_token(config)
               + attention * 2 * (t / 2) * hq * 2 * (d // hq))
    return 3 * forward


def flash_train_step(config: dict, traffic: dict) -> dict:
    """The three flash kernels of one training step in the attention
    layers, over the causal half of the T x T square at the QUERY heads'
    count (keys and values reach the kernels repeated): forward 2 products,
    dq 3, dkv 4. Bytes: q, k, v, o once forward; q, k, v, o, do read and
    dq, dk, dv written backward (bf16, at the repeated width)."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    d = int(config["hidden_size"])           # query heads x head_dim
    _, attention = _layers(config)
    per_product = 2 * b * t * t * d / 2
    tensor = b * t * d * 2
    return {"flops": attention * 9 * per_product, "bytes": attention * 12 * tensor,
            "peak": "bf16_flops_per_s"}


def ssd_train_step(config: dict, traffic: dict) -> dict:
    """The scan of one training step, all state-space layers: its products
    forward and twice backward (the gradient of either operand of each).
    Bytes: x (bf16), B and C (bf16), dt (float32) and y (float32) once, and
    the gradient of each once. What `remat` runs again is not counted."""
    tokens = int(traffic["batch_rows"]) * int(traffic["seq_len"])
    h, p, g, n, _ = _mamba(config)
    mamba, _ = _layers(config)
    per_token = h * p * 2 + 2 * g * n * 2 + h * 4 + h * p * 4
    return {"flops": mamba * tokens * 3 * scan_flops_per_token(config),
            "bytes": mamba * tokens * 2 * per_token, "peak": "bf16_flops_per_s"}
