"""An `evabyte`-shaped decoder (EVA attention: causal softmax inside a window
of `window_size` plus one pooled key and value a chunk of `chunk_size` of
every window before it; gated MLPs `intermediate_size` wide; an untied head
of `num_pred_heads` x `vocab_size` outputs) from the published keys. The
traffic gives `batch_rows` and `seq_len`."""

from __future__ import annotations

# the edge of a score tile of the flash kernels at heads of 128 in bfloat16
# (ops/flash_attention.plan_flash's choice; tests/test_evabyte_family.py
# holds the two together): a kernel computes whole tiles, so its work is
# counted in them
TILE = 512


def _sizes(config: dict):
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return d, h, d // h, int(config["window_size"]), int(config["chunk_size"])


def layer_matmul_params(config: dict) -> int:
    """W_q, W_k, W_v, W_o and the MLP's three; norms, phi and mu are in no
    matrix product."""
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    return 4 * d * d + 3 * d * f


def matmul_params(config: dict) -> int:
    """Parameters in a product for one byte, over all layers held, and the
    head (the embedding is a lookup)."""
    d = int(config["hidden_size"])
    return (int(config["num_hidden_layers"]) * layer_matmul_params(config)
            + d * int(config["num_pred_heads"]) * int(config["vocab_size"]))


def score_entries_per_head(config: dict, seq_len: int):
    """(local, remote) score entries one head computes over a row, before
    any tile rounding: each window's causal half (the diagonal with it), and
    for the queries of window w the `window / chunk` summaries of each of
    the w windows before it. A last window may be part-filled."""
    _, _, _, window, chunk = _sizes(config)
    fills = [min(window, seq_len - start) for start in range(0, seq_len, window)]
    return (sum(n * (n + 1) // 2 for n in fills),
            sum(n * w * (window // chunk) for w, n in enumerate(fills)))


def live_tiles_per_head(config: dict, seq_len: int):
    """(local, remote) TILE x TILE score tiles with at least one entry the
    mask lets through, one head, one row: a window's causal square has n (n
    + 1) / 2 of n^2, n = window / TILE; over the summaries, query tile qi
    and key tile ki meet iff ki's first summary lies in a window before
    qi's last query's."""
    _, _, _, window, chunk = _sizes(config)
    assert window % TILE == 0 and seq_len % window == 0
    n, per_window = window // TILE, window // chunk
    local = (seq_len // window) * (n * (n + 1) // 2)
    k_tiles = -(-(seq_len // chunk) // TILE)
    remote = sum((ki * TILE) // per_window < (qi * TILE + TILE - 1) // window
                 for qi in range(seq_len // TILE) for ki in range(k_tiles))
    return local, remote


def train_flops_per_item(config: dict, traffic: dict) -> float:
    """Per byte. Weights: 2 ops per parameter in a product. EVA attention in
    each layer: QK^T and PV over the score entries the definition has (the
    causal half of a window, the summaries of the windows before), 2 * 2 *
    head_dim each, and the pooling's three sums (the weight k . phi and the
    two weighted sums: 6 * head_dim a head). Times 3 for forward and
    backward; what `remat` runs again is not counted."""
    d, h, hd, _, _ = _sizes(config)
    t = int(traffic["seq_len"])
    local, remote = score_entries_per_head(config, t)
    attention = h * ((local + remote) / t * 2 * 2 * hd + 6 * hd)
    forward = 2 * matmul_params(config) + int(config["num_hidden_layers"]) * attention
    return 3 * forward


def flash_train_step(config: dict, traffic: dict) -> dict:
    """The flash kernels of one training step, all layers: ps_flash_fwd and
    the fused ps_flash_dqkv, once over the windows and once over the
    summaries, SEVEN products a live tile (forward QK^T and PV; backward the
    scores again, dP, dV, dK, dQ: since the fused backward a tile's scores
    are made once for all three gradients). Tiles as the kernels run them:
    whole TILE x TILE tiles wherever the mask lets one entry through. Bytes:
    over the windows q, k, v, o once forward, q, k, v, o, do read and dq,
    dk, dv written backward (12 arrays); over the summaries q read and o
    written forward, q and do read and dq written backward (5 arrays), and
    the pooled keys and values and their gradients at a `chunk`-th (6
    arrays); bf16."""
    b, t = int(traffic["batch_rows"]), int(traffic["seq_len"])
    d, h, hd, _, chunk = _sizes(config)
    layers = int(config["num_hidden_layers"])
    tiles = sum(live_tiles_per_head(config, t))
    array = b * t * d * 2
    return {"flops": layers * b * h * tiles * 7 * 2 * TILE * TILE * hd,
            "bytes": layers * (12 + 5 + 6 / chunk) * array, "peak": "bf16_flops_per_s"}

