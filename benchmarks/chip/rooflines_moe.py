"""What the routed experts' products need from their shapes, for either scoring model: the
work of the Pallas calls named ``moe_grouped_fwd``. Counted per expert layer of the cut:
``2 * tokens * top-k * 3 * d * h`` (gate, up and down over every (token, expert) pair), on
the rows the router really chose: no padding of an expert's group to whole blocks, no
shared expert, no router. The kernel multiplies padded blocks and is timed on all of them,
so its share of the peak reads under 100 by construction. ``rooflines.py`` keeps the chip's
peaks."""


def expert_layers(config: dict) -> int:
    """Layers of the cut that route: all but the dense ones, and each MTP module's."""
    dense = config.get("num_dense_layers", config.get("first_k_dense_replace", 0))
    return config["num_hidden_layers"] - dense + config.get("num_nextn_predict_layers", 0)


def routed_flops(config: dict) -> float:
    c = config
    pairs = c["tokens"] * c["num_experts_per_tok"]
    return 2.0 * pairs * 3 * c["hidden_size"] * c["moe_intermediate_size"] * expert_layers(c)


def routed_floor_s(config: dict, peak: dict, chips: int) -> float:
    """Every expert layer's routed products at the bf16 MXU peak."""
    return routed_flops(config) / (chips * peak["bf16_flops_per_s"])
