"""A `deepseek_v3`-shaped decoder (latent attention, sigmoid-routed experts
with shared experts, gated MLPs, untied head) from the published keys, on a
chip's share: `experts_held` of `n_routed_experts`, `vocab_size` the slice
held. The traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations


def _sizes(config: dict):
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"]),
            int(config["v_head_dim"]), int(config["kv_lora_rank"]))


def _layers(config: dict):
    dense = int(config["first_k_dense_replace"])
    return dense, int(config["num_hidden_layers"]) - dense


def attention_params(config: dict) -> int:
    d, h, dqk, dv, r = _sizes(config)
    rope = int(config["qk_rope_head_dim"])
    return d * h * dqk + d * (r + rope) + r * h * (dqk - rope + dv) + h * dv * d


def routed_rows_share(config: dict) -> float:
    """Rows a token sends to the experts held here, at uniform routing."""
    return int(config["num_experts_per_tok"]) * int(config["experts_held"]) / int(
        config["n_routed_experts"])


def active_matmul_params(config: dict) -> float:
    """Parameters in a product for one token, over all layers held: the
    routed experts at `routed_rows_share` experts a token."""
    d = int(config["hidden_size"])
    f = int(config["moe_intermediate_size"])
    dense, sparse = _layers(config)
    per_dense = attention_params(config) + 3 * d * int(config["intermediate_size"])
    per_sparse = (attention_params(config) + d * int(config["n_routed_experts"])
                  + 3 * d * f * int(config["n_shared_experts"])
                  + routed_rows_share(config) * 3 * d * f)
    return dense * per_dense + sparse * per_sparse + d * int(config["vocab_size"])  # head


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per token. Weights: 2 ops per parameter in a product (the embedding
    is a lookup). Causal attention: QK^T over half the square at the
    query/key width, PV at the value width: 2 * (T/2) * heads * (d_qk +
    d_v) per token and layer. Times 3 for forward and backward."""
    _, h, dqk, dv, _ = _sizes(config)
    t = int(traffic["seq_len"])
    layers = int(config["num_hidden_layers"])
    forward = 2 * active_matmul_params(config) + layers * 2 * (t / 2) * h * (dqk + dv)
    return 3 * forward


def flash_train_step(config: dict, traffic: dict) -> dict:
    """The three flash kernels of one training step, all layers, over the
    causal half of the T x T square, each product at its real width:
    forward QK^T (d_qk) and PV (d_v); dq scores again (d_qk), dP (d_v), dQ
    (d_qk); dkv scores again (d_qk), dP (d_v), dV (d_v), dK (d_qk). Bytes:
    q, k, v, o once forward; q, k, v, o, do read and dq, dk, dv written
    backward (bf16)."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    _, h, dqk, dv, _ = _sizes(config)
    layers = int(config["num_hidden_layers"])
    half_square = 2 * b * h * t * t / 2           # ops per unit of width
    widths = (dqk + dv) + (dqk + dv + dqk) + (dqk + dv + dv + dqk)
    qk, vo = b * t * h * dqk * 2, b * t * h * dv * 2   # one bf16 array of each width
    bytes_ = (2 * qk + 2 * vo) + (2 * qk + 3 * vo) + (2 * qk + vo)
    return {"flops": layers * half_square * widths, "bytes": layers * bytes_,
            "peak": "bf16_flops_per_s"}


def moe_routed_train_step(config: dict, traffic: dict, counted: dict = None) -> dict:
    """The grouped products of the routed experts held here, one training
    step, all expert layers, for the rows really routed here:
    `counted["moe_rows_here_traced"]`, the step's own counter summed over
    the layers (a dropless layer's work follows its router); without
    `counted`, uniform routing: N * k * held / all rows a layer. A row goes
    through gate, up and down (3 * d * f parameters), forward, the gradient
    of the rows and the gradient of the weights: 3 products of 2 ops per
    row and parameter. What `remat` runs again is not counted. Bytes: the
    rows in and out of each product (bf16) and each held expert's float32
    gradient written once, rows or none. The matrices read are left out:
    an expert without rows reads none, and the counters do not say how many
    have rows (16 x 9.4 MB a layer and pass, 2.9 ms a step were all read:
    under the 7.1 ms of the operations at uniform routing), so the share
    errs low, never high."""
    n = int(traffic["batch_rows"]) * int(traffic["seq_len"])
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    held = int(config["experts_held"])
    _, sparse = _layers(config)
    rows = (sparse * n * routed_rows_share(config) if counted is None
            else float(counted["moe_rows_here_traced"]))
    flops = 3 * 2 * rows * 3 * d * f
    # per product pass over the three matrices, rows of width d and f move once each way
    row_bytes = 3 * 3 * rows * (d + f) * 2
    grad_bytes = sparse * held * 3 * d * f * 4
    return {"flops": flops, "bytes": row_bytes + grad_bytes, "peak": "bf16_flops_per_s"}
