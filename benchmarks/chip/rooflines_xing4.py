"""What the Xing4 scoring forward needs from its shapes: floating-point operations of
one solve, counted from the configuration's published keys. Multiply-adds count two,
the causal half of the attention scores is counted and not the masked half, elementwise
work (norms, rotary positions, softmax, Sinkhorn, stream mixing) counts nothing, and
only the positions that score the continuation go through the head. ``rooflines.py``
keeps the chip's peaks."""


def attention_core_flops(config: dict) -> float:
    """``q k^T`` and ``p v`` of every layer (the MTP module's included) over the causal
    pairs ``T (T + 1) / 2``: the work of the Pallas calls named ``mla_flash_fwd``."""
    t = config["tokens"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    layers = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    return 2.0 * (t * (t + 1) / 2) * width * config["num_attention_heads"] * layers


def forward_flops(config: dict) -> float:
    c = config
    t, d, h = c["tokens"], c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n = c["hc_mult"]
    projections = 2.0 * t * (d * c["q_lora_rank"] + c["q_lora_rank"] * h * (nope + rope)
                             + d * (c["kv_lora_rank"] + rope)
                             + c["kv_lora_rank"] * h * (nope + v) + h * v * d)
    mappings = 2 * 2.0 * t * (n * d) * (2 * n + n * n)  # two sub-blocks a layer
    dense = 2.0 * t * 3 * d * c["intermediate_size"]
    experts = 2.0 * t * 3 * d * c["moe_intermediate_size"] * (
        c["num_experts_per_tok"] + c["n_shared_experts"]) + 2.0 * t * d * c["n_routed_experts"]
    n_dense = c["first_k_dense_replace"]
    n_expert = c["num_hidden_layers"] - n_dense + c["num_nextn_predict_layers"]
    layers = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    mtp_projection = 2.0 * t * 2 * d * d * c["num_nextn_predict_layers"]
    heads = 2.0 * c["continuation"] * d * c["vocab_size"] * (1 + c["num_nextn_predict_layers"])
    return (attention_core_flops(c) + layers * (projections + mappings) + n_dense * dense
            + n_expert * experts + mtp_projection + heads)


def forward_floor_s(config: dict, peak: dict, chips: int) -> float:
    """The whole forward at the bf16 MXU peak."""
    return forward_flops(config) / (chips * peak["bf16_flops_per_s"])


def attention_core_floor_s(config: dict, peak: dict, chips: int) -> float:
    return attention_core_flops(config) / (chips * peak["bf16_flops_per_s"])
