"""A `laguna`-shaped decoder (grouped-query attention whose layers are
`full_attention` or `sliding_attention` by `layer_types`, each with its own
number of query heads by `num_attention_heads_per_layer`, a sigmoid gate a
head on the attention output, a dense gated MLP or sigmoid-routed experts
with a shared expert by `mlp_layer_types`, untied head) from the published
keys, on a chip's share: `experts_held` of `num_experts`, `vocab_size` the
slice held. The traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations

SLIDING = "sliding_attention"


def _layers(config: dict):
    """[(kind, query heads, ffn kind)] a layer held."""
    return list(zip(config["layer_types"], map(int, config["num_attention_heads_per_layer"]),
                    config["mlp_layer_types"]))


def attention_matmul_params(config: dict, heads: int) -> int:
    """W_q, W_k, W_v, the gate's W_g and W_o of a layer of `heads` query
    heads; the norms are in no product."""
    d, hd = int(config["hidden_size"]), int(config["head_dim"])
    kv = int(config["num_key_value_heads"])
    return 2 * d * heads * hd + 2 * d * kv * hd + d * heads


def routed_rows_share(config: dict) -> float:
    """Rows a token sends to the experts held here, at uniform routing."""
    return int(config["num_experts_per_tok"]) * int(config["experts_held"]) / int(
        config["num_experts"])


def active_matmul_params(config: dict) -> float:
    """Parameters in a product for one token, over all layers held: the
    routed experts at `routed_rows_share` experts a token; the untied head
    (the embedding is a lookup)."""
    d, f = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    sparse = (d * int(config["num_experts"])
              + 3 * d * int(config["shared_expert_intermediate_size"])
              + routed_rows_share(config) * 3 * d * f)
    total = d * int(config["vocab_size"])
    for _, heads, ffn in _layers(config):
        total += attention_matmul_params(config, heads)
        total += 3 * d * int(config["intermediate_size"]) if ffn == "dense" else sparse
    return total


def score_entries(config: dict, kind: str, seq_len: int) -> int:
    """Score entries one head computes over a row of `seq_len` under the
    layer kind's mask, as the definition has them (no tile rounding): the
    causal half with the diagonal, or under a window the `sliding_window`
    latest keys of each query (fewer for the first queries)."""
    if kind != SLIDING:
        return seq_len * (seq_len + 1) // 2
    w = min(int(config["sliding_window"]), seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per token. Weights: 2 ops per parameter in a product. Attention in
    each layer: QK^T and PV over the score entries its mask keeps, 2 * 2 *
    head_dim a head and entry. Times 3 for forward and backward; what
    `remat` runs again is not counted."""
    t, hd = int(traffic["seq_len"]), int(config["head_dim"])
    attention = sum(heads * score_entries(config, kind, t) / t * 2 * 2 * hd
                    for kind, heads, _ in _layers(config))
    return 3 * (2 * active_matmul_params(config) + attention)


def _flash(config: dict, traffic: dict, kinds) -> dict:
    """ps_flash_fwd and the fused ps_flash_dqkv of one training step in the
    layers whose kind is in `kinds`: SEVEN products an entry the mask keeps
    (forward QK^T and PV; backward the scores again, dP, dV, dK, dQ), each 2
    * head_dim operations, counted over the ENTRIES and not the tiles, so
    that a tile the band fills by half reads as half. Bytes: q, k, v, o once
    forward; q, k, v, o, do read and dq, dk, dv written backward (12 arrays,
    bf16), keys and values at the query heads' width, as the kernels are
    handed them."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    hd = int(config["head_dim"])
    flops = bytes_ = 0
    for kind, heads, _ in _layers(config):
        if kind in kinds:
            flops += b * heads * score_entries(config, kind, t) * 7 * 2 * hd
            bytes_ += 12 * b * t * heads * hd * 2
    return {"flops": flops, "bytes": bytes_, "peak": "bf16_flops_per_s"}


def flash_train_step(config: dict, traffic: dict) -> dict:
    """The flash kernels of one training step, both layer kinds."""
    return _flash(config, traffic, set(config["layer_types"]))


def swa_flash_train_step(config: dict, traffic: dict) -> dict:
    """The flash kernels of the sliding layers alone."""
    return _flash(config, traffic, {SLIDING})


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
    sparse = sum(1 for _, _, ffn in _layers(config) if ffn == "sparse")
    rows = (sparse * n * routed_rows_share(config) if counted is None
            else float(counted["moe_rows_here_traced"]))
    flops = 3 * 2 * rows * 3 * d * f
    row_bytes = 3 * 3 * rows * (d + f) * 2
    grad_bytes = sparse * held * 3 * d * f * 4
    return {"flops": flops, "bytes": row_bytes + grad_bytes, "peak": "bf16_flops_per_s"}
