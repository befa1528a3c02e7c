"""What the Trinity scoring forward needs from its shapes: floating-point operations of
one solve, counted from the configuration's published keys. Multiply-adds count two; of
the attention scores only the pairs a row may see are counted, the causal half on a
``full_attention`` layer and the band ``i - window < j <= i`` on a ``sliding_attention``
one, whatever blocks a kernel visits; elementwise work (norms, rotary positions, softmax,
the gate's sigmoid) counts nothing; only the positions that score the continuation go
through the head. ``rooflines.py`` keeps the chip's peaks."""


def _layers_of(config: dict, kind: str) -> int:
    return sum(k == kind for k in config["layer_types"])


def _core_flops(config: dict, pairs: float, layers: int) -> float:
    """``q k^T`` and ``p v`` over ``pairs`` (row, key) pairs a head."""
    return 2.0 * pairs * 2 * config["head_dim"] * config["num_attention_heads"] * layers


def window_pairs(config: dict) -> float:
    t, w = config["tokens"], min(config["sliding_window"], config["tokens"])
    return w * (w + 1) / 2 + (t - w) * w


def window_core_flops(config: dict) -> float:
    """The work of the Pallas calls named ``swa_flash_fwd``: the band's pairs."""
    return _core_flops(config, window_pairs(config), _layers_of(config, "sliding_attention"))


def full_core_flops(config: dict) -> float:
    """The work of the Pallas calls named ``gqa_flash_fwd``: the causal pairs."""
    t = config["tokens"]
    return _core_flops(config, t * (t + 1) / 2, _layers_of(config, "full_attention"))


def forward_flops(config: dict) -> float:
    c = config
    t, d = c["tokens"], c["hidden_size"]
    wide = c["num_attention_heads"] * c["head_dim"]
    narrow = c["num_key_value_heads"] * c["head_dim"]
    projections = 2.0 * t * d * (3 * wide + 2 * narrow)  # q, gate and o; k and v
    dense = 2.0 * t * 3 * d * c["intermediate_size"]
    experts = 2.0 * t * 3 * d * c["moe_intermediate_size"] * (
        c["num_experts_per_tok"] + c["num_shared_experts"]) + 2.0 * t * d * c["num_experts"]
    n_dense = c["num_dense_layers"]
    head = 2.0 * c["continuation"] * d * c["vocab_size"]
    return (window_core_flops(c) + full_core_flops(c) + c["num_hidden_layers"] * projections
            + n_dense * dense + (c["num_hidden_layers"] - n_dense) * experts + head)


def forward_floor_s(config: dict, peak: dict, chips: int) -> float:
    """The whole forward at the bf16 MXU peak."""
    return forward_flops(config) / (chips * peak["bf16_flops_per_s"])


def window_core_floor_s(config: dict, peak: dict, chips: int) -> float:
    return window_core_flops(config) / (chips * peak["bf16_flops_per_s"])


def full_core_floor_s(config: dict, peak: dict, chips: int) -> float:
    return full_core_flops(config) / (chips * peak["bf16_flops_per_s"])
