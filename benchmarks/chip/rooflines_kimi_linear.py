"""What the Kimi-Linear scoring forward needs from its shapes: floating-point operations and
bytes of one solve, counted from the configuration's keys. Multiply-adds count two; of the
latent layer's scores the causal half is counted; the recurrence of a KDA layer counts what
the token-by-token form does (decay aside): ``k^T S``, the rank-one update and ``S^T q``,
``6 d_k d_v`` a token and head, whatever form a kernel takes; elementwise work (norms,
convolution, softplus, softmax, gates) counts nothing; only the positions that score the
continuation go through the head. The layer kinds are the published lists of
``linear_attn_config`` (1-indexed) cut to ``num_hidden_layers``. ``num_experts`` is the number
of experts held here and ``published.num_experts`` the router's width. ``rooflines.py`` keeps
the chip's peaks."""


def _kinds(config: dict):
    """(KDA layers, latent layers, dense layers, expert layers) of the cut."""
    n = config["num_hidden_layers"]
    latent = sum(1 for i in config["linear_attn_config"]["full_attn_layers"] if i <= n)
    dense = config["first_k_dense_replace"]
    return n - latent, latent, dense, n - dense


def _kda_elements(config: dict) -> int:
    """(T, heads, head_dim) of one KDA layer's streams."""
    lin = config["linear_attn_config"]
    return config["tokens"] * lin["num_heads"] * lin["head_dim"]


def kda_flops(config: dict) -> float:
    """The recurrence of every KDA layer: ``6 d_k d_v`` a token and head."""
    return 6.0 * config["linear_attn_config"]["head_dim"] * _kda_elements(config) * _kinds(config)[0]


def kda_bytes(config: dict) -> float:
    """The recurrence's operands once through HBM at the stated types, every KDA layer: q, k,
    v and o bfloat16, the decay's pre-activation and the channel gate's float32, each (T,
    heads, head_dim)."""
    return _kda_elements(config) * (4 * 2 + 2 * 4) * _kinds(config)[0]


def mla_core_flops(config: dict) -> float:
    """``q k^T`` and ``p v`` of the latent layers over the causal pairs ``T (T + 1) / 2``:
    the work of the Pallas calls named ``mla_flash_fwd``."""
    t = config["tokens"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return 2.0 * (t * (t + 1) / 2) * width * config["num_attention_heads"] * _kinds(config)[1]


def routed_flops_of(config: dict, pairs: float) -> float:
    """Gate, up and down products over ``pairs`` (token, held expert) pairs."""
    return 2.0 * pairs * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def mean_held_pairs(config: dict) -> float:
    """(token, expert) pairs an expert layer routes to the experts held here when the load is
    even: the held share of ``tokens * top-k``."""
    share = config["num_experts"] / config["published"]["num_experts"]
    return config["tokens"] * config["num_experts_per_token"] * share


def _kda_weights(config: dict) -> int:
    """q, k, v, o; the decay's and the gate's rank-``head_dim`` pairs; beta."""
    d, lin = config["hidden_size"], config["linear_attn_config"]
    wide, rank = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    return 4 * d * wide + 2 * (d * rank + rank * wide) + d * lin["num_heads"]


def _mla_weights(config: dict) -> int:
    """q, kv_a, kv_b, o."""
    c = config
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
                        c["kv_lora_rank"])
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) + h * v * d


def parameters(config: dict) -> int:
    """Parameters the cut holds on the chip."""
    c = config
    d, lin = c["hidden_size"], c["linear_attn_config"]
    kda, latent, dense, expert = _kinds(c)
    wide = lin["num_heads"] * lin["head_dim"]
    kda_attention = (_kda_weights(c) + 3 * lin["short_conv_kernel_size"] * wide
                     + lin["num_heads"] + wide + lin["head_dim"])  # convolutions, A_log, dt_bias, norm
    mla_attention = _mla_weights(c) + c["kv_lora_rank"]
    expert_width = 3 * d * c["moe_intermediate_size"]
    router = d * c["published"]["num_experts"] + c["published"]["num_experts"]
    expert_layer = ((c["num_experts"] + c["num_shared_experts"]) * expert_width + router)
    norms = (2 * c["num_hidden_layers"] + 1) * d
    return (kda * kda_attention + latent * mla_attention + dense * 3 * d * c["intermediate_size"]
            + expert * expert_layer + 2 * c["vocab_size"] * d + norms)


def forward_flops(config: dict) -> float:
    """One solve, the routed experts at their mean load."""
    c = config
    t, d = c["tokens"], c["hidden_size"]
    kda, latent, dense, expert = _kinds(c)
    expert_ffn = (routed_flops_of(c, mean_held_pairs(c))
                  + 2.0 * t * 3 * d * c["moe_intermediate_size"] * c["num_shared_experts"]
                  + 2.0 * t * d * c["published"]["num_experts"])
    head = 2.0 * c["continuation"] * d * c["vocab_size"]
    return (kda_flops(c) + mla_core_flops(c) + kda * 2.0 * t * _kda_weights(c)
            + latent * 2.0 * t * _mla_weights(c) + dense * 2.0 * t * 3 * d * c["intermediate_size"]
            + expert * expert_ffn + head)


def forward_floor_s(config: dict, peak: dict, chips: int) -> float:
    """The whole forward at the bf16 MXU peak."""
    return forward_flops(config) / (chips * peak["bf16_flops_per_s"])


def kda_floor_s(config: dict, peak: dict, chips: int) -> float:
    """The larger of the recurrence's own work at the MXU peak and its operands once through
    HBM (the bytes bound it: 16 bytes for 6 x 128 operations an element)."""
    return max(kda_flops(config) / (chips * peak["bf16_flops_per_s"]),
               kda_bytes(config) / (chips * peak["hbm_bytes_per_s"]))


def mla_core_floor_s(config: dict, peak: dict, chips: int) -> float:
    return mla_core_flops(config) / (chips * peak["bf16_flops_per_s"])


def routed_floor_s(config: dict, peak: dict, chips: int, pairs: float) -> float:
    """``pairs`` counted (token, held expert) pairs through gate, up and down at the bf16 MXU
    peak: no padding of a group to whole blocks, no shared expert, no router."""
    return routed_flops_of(config, pairs) / (chips * peak["bf16_flops_per_s"])
