"""What the DeepSeek-V3.2-Exp scoring forward needs from its shapes: floating-point
operations of one solve, counted from the configuration's keys. Multiply-adds count two.
The attention over the selection counts the **selected** (query, key) pairs only, ``sum_t
min(index_topk, t + 1)``, whatever a kernel multiplies besides; the indexer counts its
``index_n_heads`` products over the causal pairs ``T (T + 1) / 2`` and nothing of its
selection (ReLU, weighted sum, ranking: vector work); elementwise work (norms, rotary
positions, softmax) counts nothing; only the positions that score the continuation go
through the head. ``n_routed_experts`` is the number of experts held here and
``published.n_routed_experts`` the router's width. ``rooflines.py`` keeps the chip's peaks."""


def selected_pairs(config: dict) -> float:
    """(query, key) pairs one layer's selection keeps: ``sum_t min(index_topk, t + 1)``."""
    t, k = config["tokens"], min(config["index_topk"], config["tokens"])
    return k * (k + 1) / 2 + (t - k) * k


def causal_pairs(config: dict) -> float:
    return config["tokens"] * (config["tokens"] + 1) / 2


def index_flops(config: dict) -> float:
    """``q_j . k`` of every index head over the causal pairs, every layer: the products of the
    Pallas calls named ``dsa_index_fwd``."""
    return (2.0 * config["index_n_heads"] * config["index_head_dim"] * causal_pairs(config)
            * config["num_hidden_layers"])


def selected_flops(config: dict) -> float:
    """``q k^T`` and ``p v`` of every head over the selected pairs, every layer: the least
    work of the Pallas calls named ``dsa_flash_fwd``."""
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return (2.0 * width * config["num_attention_heads"] * selected_pairs(config)
            * config["num_hidden_layers"])


def routed_flops_of(config: dict, pairs: float) -> float:
    """Gate, up and down products over ``pairs`` (token, held expert) pairs."""
    return 2.0 * pairs * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def mean_held_pairs(config: dict) -> float:
    """(token, expert) pairs an expert layer routes to the experts held here when the load
    is even: the held share of ``tokens * top-k``."""
    share = config["n_routed_experts"] / config["published"]["n_routed_experts"]
    return config["tokens"] * config["num_experts_per_tok"] * share


def _widths(config: dict):
    c = config
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def parameters(config: dict) -> dict:
    """Parameters the cut holds on the chip, by part (the configuration's ``bytes``)."""
    c = config
    d, h, rq, rkv, nope, rope, v = _widths(c)
    ih, ihd = c["index_n_heads"], c["index_head_dim"]
    dense, layers = c["first_k_dense_replace"], c["num_hidden_layers"]
    wide = c["published"]["n_routed_experts"]
    attention = (d * rq + rq * h * (nope + rope) + d * (rkv + rope) + rkv * h * (nope + v)
                 + h * v * d + rq + rkv)
    indexer = rq * ih * ihd + d * ihd + d * ih + 2 * ihd
    expert_ffn = ((c["n_routed_experts"] + c["n_shared_experts"]) * 3 * d
                  * c["moe_intermediate_size"] + d * wide + wide)
    parts = {"latent_attention": attention, "indexer": indexer, "expert_feed_forward": expert_ffn,
             "dense_feed_forward": 3 * d * c["intermediate_size"],
             "vocabulary": 2 * c["vocab_size"] * d, "norms": (2 * layers + 1) * d}
    parts["total"] = (layers * (attention + indexer) + dense * parts["dense_feed_forward"]
                      + (layers - dense) * expert_ffn + parts["vocabulary"] + parts["norms"])
    return parts


def forward_flops(config: dict) -> float:
    """One solve at the least work: selected pairs only, the routed experts at their mean
    load."""
    c = config
    t = c["tokens"]
    d, h, rq, rkv, nope, rope, v = _widths(c)
    dense, layers = c["first_k_dense_replace"], c["num_hidden_layers"]
    projections = 2.0 * t * (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
                             + rkv * h * (nope + v) + h * v * d)
    index_projections = 2.0 * t * (rq * c["index_n_heads"] * c["index_head_dim"]
                                   + d * c["index_head_dim"] + d * c["index_n_heads"])
    dense_ffn = 2.0 * t * 3 * d * c["intermediate_size"]
    expert_ffn = (routed_flops_of(c, mean_held_pairs(c))
                  + 2.0 * t * 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
                  + 2.0 * t * d * c["published"]["n_routed_experts"])
    head = 2.0 * c["continuation"] * d * c["vocab_size"]
    return (index_flops(c) + selected_flops(c) + layers * (projections + index_projections)
            + dense * dense_ffn + (layers - dense) * expert_ffn + head)


def forward_floor_s(config: dict, peak: dict, chips: int) -> float:
    """The whole forward's least work at the bf16 MXU peak."""
    return forward_flops(config) / (chips * peak["bf16_flops_per_s"])


def index_floor_s(config: dict, peak: dict, chips: int) -> float:
    return index_flops(config) / (chips * peak["bf16_flops_per_s"])


def selected_floor_s(config: dict, peak: dict, chips: int) -> float:
    return selected_flops(config) / (chips * peak["bf16_flops_per_s"])

