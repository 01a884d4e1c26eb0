"""A `kimi_linear`-shaped decoder (Kimi Delta Attention layers and latent
attention layers without rotation by the two published lists, sigmoid-routed
experts with a shared expert, gated MLPs, untied head) from the published
keys, on a chip's share: `experts_held` of `num_experts`, `vocab_size` the
slice held. The traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations

KDA_CHUNK = 64   # the program's default where the file has no `kda_chunk_size`


def _kda(config: dict):
    lin = config["linear_attn_config"]
    return (int(lin["num_heads"]), int(lin["head_dim"]),
            int(config.get("kda_chunk_size", KDA_CHUNK)))


def _mla(config: dict):
    return (int(config["num_attention_heads"]),
            int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"]),
            int(config["v_head_dim"]), int(config["kv_lora_rank"]))


def _layers(config: dict):
    """(KDA layers, latent-attention layers, dense-FFN layers, expert layers)."""
    lin = config["linear_attn_config"]
    dense = int(config["first_k_dense_replace"])
    return (len(lin["kda_layers"]), len(lin["full_attn_layers"]), dense,
            int(config["num_hidden_layers"]) - dense)


def kda_matmul_params(config: dict) -> int:
    """W_q, W_k, W_v, W_o, the two low-rank pairs (`head_dim` wide) and
    W_beta; the convs' taps, the decay vectors and the norms are in no
    product."""
    d = int(config["hidden_size"])
    h, dk, _ = _kda(config)
    inner = h * dk
    return 4 * d * inner + 2 * (d * dk + dk * inner) + d * h


def mla_matmul_params(config: dict) -> int:
    d = int(config["hidden_size"])
    h, dqk, dv, r = _mla(config)
    shared = int(config["qk_rope_head_dim"])
    return d * h * dqk + d * (r + shared) + r * h * (dqk - shared + dv) + h * dv * d


def routed_rows_share(config: dict) -> float:
    """Rows a token sends to the experts held here, at uniform routing."""
    return int(config["num_experts_per_token"]) * int(config["experts_held"]) / int(
        config["num_experts"])


def active_matmul_params(config: dict) -> float:
    """Parameters in a product for one token, over all layers held: the
    routed experts at `routed_rows_share` experts a token; the untied head
    (the embedding is a lookup)."""
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    kda, mla, dense, sparse = _layers(config)
    per_sparse = (d * int(config["num_experts"]) + 3 * d * f * int(config["num_shared_experts"])
                  + routed_rows_share(config) * 3 * d * f)
    return (kda * kda_matmul_params(config) + mla * mla_matmul_params(config)
            + dense * 3 * d * int(config["intermediate_size"]) + sparse * per_sparse
            + d * int(config["vocab_size"]))


def kda_flops_per_token(config: dict) -> float:
    """The chunked delta rule's operations for one token of one KDA layer,
    forward, all heads; C the chunk, K = V the head width. Per head: the two
    decayed score squares k k^T and q k^T over the chunk (2 * 2 C K, the
    whole square counted as the products compute it); the unit triangular
    system's inverse (2 C^2 / 3) and its two right-hand sides K o exp(G) and
    V (2 C K + 2 C V); the chunk's start state read by the keys and by the
    queries and its end state made (3 * 2 K V); the corrections weighted
    into the outputs (2 C V)."""
    h, dk, c = _kda(config)
    per_head = 4 * c * dk + 2 * c * c / 3 + 2 * c * dk + 2 * c * dk + 6 * dk * dk + 2 * c * dk
    return h * per_head


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per token. Weights: 2 ops per parameter in a product. The delta
    rule's own operations in each KDA layer. Causal attention in each latent
    attention layer: QK^T over half the square at the query/key width, PV
    at the value width. Times 3 for forward and backward."""
    h, dqk, dv, _ = _mla(config)
    t = int(traffic["seq_len"])
    kda, mla, _, _ = _layers(config)
    forward = (2 * active_matmul_params(config) + kda * kda_flops_per_token(config)
               + mla * 2 * (t / 2) * h * (dqk + dv))
    return 3 * forward


def kda_train_step(config: dict, traffic: dict) -> dict:
    """The delta rule of one training step, all KDA layers: its operations
    forward and twice backward. Bytes: q, k, v (bf16), g (float32, a key
    channel), beta (float32, a head) read and o (float32) written once, and
    the gradient of each once. What `remat` runs again is not counted."""
    tokens = int(traffic["batch_rows"]) * int(traffic["seq_len"])
    h, dk, _ = _kda(config)
    kda, _, _, _ = _layers(config)
    per_token = h * (3 * dk * 2 + dk * 4 + 4 + dk * 4)
    return {"flops": kda * tokens * 3 * kda_flops_per_token(config),
            "bytes": kda * tokens * 2 * per_token, "peak": "bf16_flops_per_s"}


def flash_train_step(config: dict, traffic: dict) -> dict:
    """The three flash kernels of one training step in the latent attention
    layers, over the causal half of the T x T square, each product at its
    real width: forward QK^T (d_qk) and PV (d_v); dq scores again (d_qk), dP
    (d_v), dQ (d_qk); dkv scores again (d_qk), dP (d_v), dV (d_v), dK
    (d_qk). Bytes: q, k, v, o once forward; q, k, v, o, do read and dq, dk,
    dv written backward (bf16)."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    h, dqk, dv, _ = _mla(config)
    _, mla, _, _ = _layers(config)
    half_square = 2 * b * h * t * t / 2           # ops per unit of width
    widths = (dqk + dv) + (dqk + dv + dqk) + (dqk + dv + dv + dqk)
    qk, vo = b * t * h * dqk * 2, b * t * h * dv * 2   # one bf16 array of each width
    bytes_ = (2 * qk + 2 * vo) + (2 * qk + 3 * vo) + (2 * qk + vo)
    return {"flops": mla * half_square * widths, "bytes": mla * bytes_,
            "peak": "bf16_flops_per_s"}


def moe_routed_train_step(config: dict, traffic: dict, counted: dict = None) -> dict:
    """The grouped products of the routed experts held here, one training
    step, all expert layers, for the rows really routed here:
    `counted["moe_rows_here_traced"]`, the step's own counter summed over
    the layers; without `counted`, uniform routing: N * k * held / all rows
    a layer. A row goes through gate, up and down (3 * d * f parameters),
    forward, the gradient of the rows and the gradient of the weights: 3
    products of 2 ops per row and parameter. What `remat` runs again is not
    counted. Bytes: the rows in and out of each product (bf16) and each held
    expert's float32 gradient written once, rows or none; the matrices read
    are left out (an expert without rows reads none), so the share errs
    low, never high."""
    n = int(traffic["batch_rows"]) * int(traffic["seq_len"])
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    held = int(config["experts_held"])
    _, _, _, sparse = _layers(config)
    rows = (sparse * n * routed_rows_share(config) if counted is None
            else float(counted["moe_rows_here_traced"]))
    flops = 3 * 2 * rows * 3 * d * f
    row_bytes = 3 * 3 * rows * (d + f) * 2
    grad_bytes = sparse * held * 3 * d * f * 4
    return {"flops": flops, "bytes": row_bytes + grad_bytes, "peak": "bf16_flops_per_s"}
