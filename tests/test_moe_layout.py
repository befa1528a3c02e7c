"""``ht.nn.MoE``'s index work (``nn/moe.py`` ``route`` and ``_layout``) against the form it
replaced in PR 34: a stable sort of the (token, expert) pairs by expert, element gathers and
scatters round it and ``take_along_axis`` for the router's weights. That form is kept here as
the oracle; the module finds the same order by counting, and its lowered program must hold no
sort and no element gather (they move one element at a time on a TPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import heat_tpu as ht

T = 96


def sorted_layout(m, chosen):
    """``MoE._layout`` as PR 32 left it: the oracle."""
    t, k = chosen.shape
    b, e = m.block_rows, m.count
    rows = -(-t * k // b) * b + e * b
    local = chosen.reshape(-1) - jnp.int32(m.first)
    local = jnp.where((local >= 0) & (local < e), local, jnp.int32(e))
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    sorted_e = local[order]
    edges = jnp.searchsorted(sorted_e, jnp.arange(e + 2, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    load = edges[1:] - edges[:-1]
    blocks = (load[:e] + (b - 1)) // b
    first_row = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(blocks * b, dtype=jnp.int32)])
    first_row = first_row.at[e].set(rows)
    rank = jnp.arange(t * k, dtype=jnp.int32) - edges[sorted_e]
    slot_sorted = jnp.where(sorted_e < e, first_row[sorted_e] + rank, jnp.int32(rows))
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(slot_sorted)
    source = jnp.zeros((rows,), jnp.int32).at[slot_sorted].set(order // k, mode="drop")
    return slot, source, first_row[:e], blocks, load[:e]


def gathered_route(m, params, u):
    """``MoE.route`` as PR 33 left it: the oracle."""
    from heat_tpu.nn.modules import contract

    scores = jax.nn.sigmoid(contract("td,de->te", u, params["router"]))
    choice = scores + params["router_bias"]
    if m.n_group > 1:
        by_group = choice.reshape(choice.shape[0], m.n_group, -1)
        group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
        _, kept = lax.top_k(group_score, m.topk_group)
        stays = jnp.any(kept[:, :, None] == jnp.arange(m.n_group, dtype=kept.dtype), axis=1)
        choice = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(choice.shape)
    _, chosen = lax.top_k(choice, m.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * jnp.float32(m.scaling)
    return chosen.astype(jnp.int32), w


def skewed_choice(seed, t, n_experts, top_k, skew=3.0):
    """``top_k`` distinct experts a token, the low experts favoured as a seeded router's are."""
    rng = np.random.default_rng(seed)
    draw = rng.gumbel(size=(t, n_experts)) - skew * np.arange(n_experts) / n_experts
    return jnp.asarray(np.argsort(-draw, axis=1)[:, :top_k].astype(np.int32))


# (n_experts, top_k, experts_held, block_rows, T, how the choices are made)
LAYOUTS = {
    "xing4": (64, 4, None, 512, T, "skewed"),
    "trinity": (128, 8, None, 512, T, "skewed"),
    "ling": (512, 8, (0, 128), 128, T, "skewed"),
    "ling_small_blocks": (512, 8, (0, 128), 8, 160, "skewed"),
    "every_pair_on_one_expert": (16, 1, None, 8, T, "one"),
    "every_token_on_the_same_two": (16, 2, (4, 8), 8, T, "same"),
    "no_pair_held_here": (32, 4, (24, 8), 8, T, "low"),
    "window_in_the_middle": (32, 4, (11, 9), 8, T, "skewed"),
    "window_at_the_end": (32, 4, (27, 5), 16, T, "skewed"),
    "pairs_no_multiple_of_a_block": (12, 3, None, 16, 37, "skewed"),
    "count_1": (8, 2, (3, 1), 8, 50, "skewed"),
    "one_token": (8, 2, None, 8, 1, "skewed"),
    "uniform": (24, 6, (6, 12), 8, 64, "uniform"),
}


@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_layout_by_counting_is_the_sorted_layout(case):
    """``slot``, ``source``, ``first_row``, ``blocks`` and ``load``: element for element and
    dtype for dtype what the stable sort by expert gives, over five draws a case."""
    n_experts, top_k, held, block_rows, t, how = LAYOUTS[case]
    m = ht.nn.MoE(8, 8, n_experts, top_k, 0, 1.0, held, block_rows)
    counted, by_sorting = jax.jit(m._layout), jax.jit(lambda c: sorted_layout(m, c))
    for seed in range(5):
        if how == "one":
            chosen = jnp.full((t, 1), 5, jnp.int32)
        elif how == "same":
            chosen = jnp.broadcast_to(jnp.asarray([9, 4], jnp.int32), (t, 2))
        elif how == "low":  # experts 0..23 only: none of the held 24..31
            chosen = skewed_choice(seed, t, 24, top_k)
        else:
            chosen = skewed_choice(seed, t, n_experts, top_k, 0.0 if how == "uniform" else 3.0)
        got, want = counted(chosen), by_sorting(chosen)
        for name, g, w in zip(("slot", "source", "first_row", "blocks", "load"), got, want):
            assert g.dtype == w.dtype == jnp.int32 and g.shape == w.shape, name
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=f"{name} {seed}")
    if how == "low":
        assert int(got[4].sum()) == 0 and int(got[0].min()) == got[1].shape[0]
    if how == "one":
        assert int(got[4][5]) == t


ROUTES = {
    "xing4": (64, 4, 1, 1, jnp.float32),
    "trinity_bfloat16": (128, 8, 1, 1, jnp.bfloat16),
    "ling_group_limit": (512, 8, 8, 4, jnp.float32),
    "ling_group_limit_bfloat16": (512, 8, 8, 4, jnp.bfloat16),
    "small_group_limit": (16, 3, 4, 2, jnp.float32),
    "small_no_limit": (16, 3, 1, 1, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(ROUTES))
def test_route_reads_its_weights_bit_equal_to_the_gather(case):
    """``(chosen, w)``: the chosen scores read by comparison are the gathered ones bit for
    bit (a selected value plus zeros), so the normalised weights are too."""
    n_experts, top_k, n_group, topk_group, dtype = ROUTES[case]
    m = ht.nn.MoE(32, 8, n_experts, top_k, 0, 2.5, (0, n_experts // 2), 8, dtype, n_group,
                  topk_group)
    params = m.init(jax.random.key(3))
    compared, gathered = jax.jit(m.route), jax.jit(lambda p, u: gathered_route(m, p, u))
    for seed in range(3):
        u = (3.0 * jax.random.normal(jax.random.key(seed), (T, 32), jnp.float32)).astype(dtype)
        (chosen, w), (want_chosen, want_w) = compared(params, u), gathered(params, u)
        assert chosen.dtype == want_chosen.dtype == jnp.int32
        assert w.dtype == want_w.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want_chosen))
        np.testing.assert_array_equal(np.asarray(w).view(np.uint32),
                                      np.asarray(want_w).view(np.uint32))


@pytest.mark.parametrize("model", ["trinity", "ling"])
def test_the_lowered_expert_layer_sorts_and_gathers_nothing(model):
    """The StableHLO of ``MoE.apply`` at the model tests' sizes: no sort, one scatter (the
    token behind each buffer row, ``source``) and, beside the fallback's read of the buffer
    through ``source`` (a CPU takes the ``jnp`` loop), one gather: the combine's rows
    ``ys[slot]``. An element gather brought back into the index work fails here, on a CPU."""
    if model == "trinity":
        from test_trinity import CFG as cfg, T as t

        m = ht.nn.MoE(cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"],
                      cfg["num_experts_per_tok"], 1, 2.5, None, 16, jnp.bfloat16)
    else:
        from test_ling import CFG as cfg, T as t

        m = ht.nn.MoE(cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"],
                      cfg["num_experts_per_tok"], 1, 2.5, (0, cfg["num_experts"] // 4), 16,
                      jnp.bfloat16, cfg["n_group"], cfg["topk_group"])
    params = jax.eval_shape(m.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((t, m.dim), jnp.bfloat16)
    text = jax.jit(lambda p, x: m.apply(p, x)).lower(params, x).as_text()
    assert "stablehlo.sort" not in text
    assert len(re.findall(r'stablehlo\.scatter"?\(', text)) == 1
    gathers = re.findall(r'stablehlo\.gather"?\(.*', text)
    rows = -(-t * m.top_k // 16) * 16 + m.count * 16
    row_gathers = [g for g in gathers if f"tensor<{rows}x{m.dim}xbf16>" in g
                   or f"tensor<{t}x{m.dim}xbf16>" in g]
    # ys[slot] (buffer rows -> pairs) and the loop fallback's x[source] (tokens -> buffer rows)
    assert len(gathers) == len(row_gathers) == 2, gathers
