"""A `smallthinker`-shaped decoder (grouped-query attention at one head count
in every layer, sliding where `sliding_window_layout[l]` is 1 and global where
0, rotary where `rope_layout[l]` is 1; a softmax-top-k router over
`moe_num_primary_experts` that reads the attention's input; ReGLU experts in
every layer, no shared expert, no dense layer; untied head) from the published
keys, on a chip's share: `experts_held` of the experts, `vocab_size` the slice
held. The traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations


def _layers(config: dict):
    """[sliding (0 | 1)] a layer held."""
    return [int(s) for s in config["sliding_window_layout"]]


def attention_matmul_params(config: dict) -> int:
    """W_q, W_k, W_v and W_o of a layer; the norms are in no product and
    the rotation is none."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    h, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    return 2 * d * h * hd + 2 * d * kv * hd


def routed_rows_share(config: dict) -> float:
    """Rows a token sends to the experts held here, at uniform routing."""
    return int(config["moe_num_active_primary_experts"]) * int(config["experts_held"]) / int(
        config["moe_num_primary_experts"])


def active_matmul_params(config: dict) -> float:
    """Parameters in a product for one token, over all layers held: the
    attention's four matrices, the router, the routed experts at
    `routed_rows_share` experts a token; the untied head (the embedding is a
    lookup)."""
    d, f = int(config["hidden_size"]), int(config["moe_ffn_hidden_size"])
    layer = (attention_matmul_params(config) + d * int(config["moe_num_primary_experts"])
             + routed_rows_share(config) * 3 * d * f)
    return len(_layers(config)) * layer + d * int(config["vocab_size"])


def score_entries(config: dict, sliding: int, seq_len: int) -> int:
    """Score entries one head computes over a row of `seq_len` under the
    layer's mask, as the definition has them (no tile rounding): the causal
    half with the diagonal, or under a window the `sliding_window_size`
    latest keys of each query (fewer for the first queries)."""
    if not sliding:
        return seq_len * (seq_len + 1) // 2
    w = min(int(config["sliding_window_size"]), seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per token. Weights: 2 ops per parameter in a product. Attention in
    each layer: QK^T and PV over the score entries its mask keeps, 2 * 2 *
    head_dim a head and entry. Times 3 for forward and backward; what
    `remat` runs again is not counted."""
    t, hd = int(traffic["seq_len"]), int(config["head_dim"])
    heads = int(config["num_attention_heads"])
    attention = sum(heads * score_entries(config, sliding, t) / t * 2 * 2 * hd
                    for sliding in _layers(config))
    return 3 * (2 * active_matmul_params(config) + attention)


def flash_train_step(config: dict, traffic: dict) -> dict:
    """ps_flash_fwd and the fused ps_flash_dqkv of one training step, every
    layer: SEVEN products an entry the layer's mask keeps (forward QK^T and
    PV; backward the scores again, dP, dV, dK, dQ), each 2 * head_dim
    operations, counted over the ENTRIES and not the tiles, so that a tile
    the band fills by half reads as half. Bytes: q, k, v, o once forward; q,
    k, v, o, do read and dq, dk, dv written backward (12 arrays, bf16), keys
    and values at the query heads' width, as the kernels are handed them."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    hd, heads = int(config["head_dim"]), int(config["num_attention_heads"])
    flops = sum(b * heads * score_entries(config, sliding, t) * 7 * 2 * hd
                for sliding in _layers(config))
    bytes_ = len(_layers(config)) * 12 * b * t * heads * hd * 2
    return {"flops": flops, "bytes": bytes_, "peak": "bf16_flops_per_s"}


def moe_routed_train_step(config: dict, traffic: dict, counted: dict = None) -> dict:
    """The grouped products of the routed experts held here, one training
    step, all layers, for the rows really routed here:
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
    d, f = int(config["hidden_size"]), int(config["moe_ffn_hidden_size"])
    held, layers = int(config["experts_held"]), len(_layers(config))
    rows = (layers * n * routed_rows_share(config) if counted is None
            else float(counted["moe_rows_here_traced"]))
    flops = 3 * 2 * rows * 3 * d * f
    row_bytes = 3 * 3 * rows * (d + f) * 2
    grad_bytes = layers * held * 3 * d * f * 4
    return {"flops": flops, "bytes": row_bytes + grad_bytes, "peak": "bf16_flops_per_s"}
