"""The grouped expert kernel and the combine kernel (``core/kernels/grouped_matmul.py``) inside
``ht.nn.MoE``, interpreted on the CPU at tile-aligned toy shapes: against the ``jnp`` loop and the
``jnp`` combine they replace on a TPU, against both plain references, over loads that no balanced
router would give, with the rows the first never wrote poisoned; their gate's reasons; their
counters. (They compile for a described v5e at the three cells' shapes in ``tests/test_kernels.py``,
the one file that loads the TPU compiler.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core.kernels import grouped_matmul

import reference_trinity
import reference_xing4

T, D, H, ROWS = 96, 256, 128, 16
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# the largest bias a router's scores (0..1) cannot outweigh
FORCE = 100.0
_KERNEL = grouped_matmul.grouped_gated_silu  # before any fixture replaces the module's name
_COMBINE = grouped_matmul.combine


def gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def _nan_word(dtype):
    """A 32-bit word of the buffer whose every element is NaN."""
    return jnp.uint32(0x7FC07FC0 if jnp.dtype(dtype).itemsize == 2 else 0x7FC00000)


def _rows_of(ys, d, dtype):
    """The word buffer ``(rows * padded, 128)`` as ``(rows, d)`` of ``dtype``: ``_words`` undone,
    as the combine undoes it."""
    tiles, padded = grouped_matmul._token_tiles(d, jnp.dtype(dtype).itemsize)
    words = ys.reshape(-1, padded * 128)[:, :tiles * 128]
    if jnp.dtype(dtype).itemsize == 4:
        return jax.lax.bitcast_convert_type(words, jnp.float32)
    low, high = grouped_matmul._halves(words)
    return jnp.concatenate([low, high], axis=1).astype(dtype)


def _poisoned(x, source, w_gate, w_up, w_down, block_expert, used, block_rows):
    """The kernel, interpreted, with every row of a block not in use set to NaN, and the
    sublane rows that pad a buffer row to whole tiles: on the chip those are never written and
    hold whatever the buffer held."""
    ys = _KERNEL(x, source, w_gate, w_up, w_down, block_expert, used, block_rows, interpret=True)
    tiles, padded = grouped_matmul._token_tiles(x.shape[1], x.dtype.itemsize)
    at = jnp.arange(ys.shape[0], dtype=jnp.int32)
    written = (at // padded < used[0] * block_rows) & (at % padded < tiles)
    return jnp.where(written[:, None], ys, _nan_word(x.dtype))


@pytest.fixture
def interpreted(monkeypatch):
    """``MoE`` takes the kernel here, interpreted and poisoned; the counters are on."""
    monkeypatch.setattr(grouped_matmul, "available", lambda interpret=False: True)
    monkeypatch.setattr(grouped_matmul, "grouped_gated_silu", _poisoned)
    monkeypatch.setattr(grouped_matmul, "combine",
                        lambda *args, **kw: _COMBINE(*args, interpret=True, **kw))
    was_on = ht.diagnostics.enabled()
    ht.diagnostics.enable()
    ht.diagnostics.reset()
    yield
    ht.diagnostics.reset()
    if not was_on:
        ht.diagnostics.disable()


def _counter(name):
    return ht.diagnostics.report()["counters"].get(name, 0)


def _layer(experts, top_k, held, dtype, load="even", seed=0):
    """A layer, its parameters with the router's bias bent to ``load``, and tokens."""
    m = ht.nn.MoE(D, H, experts, top_k, 1, 2.0, held, ROWS, dtype=dtype)
    p = m.init(jax.random.key(seed))
    first, count = held or (0, experts)
    bias = p["router_bias"]
    if load == "skewed":  # two experts take most tokens' first choices
        bias = bias.at[first].add(0.6).at[first + count - 1].add(0.3)
    elif load == "one_expert":  # every token chooses the first held expert; the rest of its k
        others = [e for e in range(experts) if not first <= e < first + count][:top_k - 1]
        if len(others) < top_k - 1:  # the whole layer is held: its last experts take the rest
            others = list(range(experts - top_k + 1, experts))
        bias = bias.at[jnp.asarray([first] + others)].add(FORCE)
    elif load == "empty_experts":  # no token chooses every other held expert
        bias = bias.at[first:first + count:2].add(-FORCE)
    elif load == "share_not_chosen":  # every token's k choices lie outside the held share
        outside = [e for e in range(experts) if not first <= e < first + count][:top_k]
        bias = bias.at[jnp.asarray(outside)].add(FORCE)
    p = dict(p, router_bias=bias)
    u = jax.random.normal(jax.random.key(seed + 1), (T, D), jnp.float32).astype(dtype)
    return m, p, u


def _reference(which, p, u, experts, top_k, held):
    if which == "xing4":
        cfg = {"n_routed_experts": experts, "num_experts_per_tok": top_k, "routed_scaling_factor": 2.0}
        return reference_xing4.moe(p, u, cfg, held)
    cfg = {"num_experts": experts, "num_experts_per_tok": top_k, "route_scale": 2.0}
    return reference_trinity.moe(p, u, cfg, held)


SHAPES = [(8, 2, None), (8, 2, (2, 4)), (16, 4, None), (16, 4, (8, 8)), (4, 1, (3, 1))]


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("experts,top_k,held", SHAPES, ids=lambda v: str(v).replace(" ", ""))
def test_the_kernel_equals_the_loop_and_meets_both_references(interpreted, experts, top_k, held, dtype):
    """The same layer through the two kernels and through the ``jnp`` loop and combine. The
    buffer's rows in use are equal to the last bit (one block's three products in the same
    order). The layer's output is the same sum of ``top_k`` float32 terms taken in another order
    (the kernel: rank by rank; XLA: its own, and a CPU contracts products into the sum), so it
    agrees within float32's rounding, and after the cast to bfloat16 in all but a few elements
    that lay on a rounding edge. Both references within float32's rounding, or bfloat16's where
    the streams are bfloat16."""
    m, p, u = _layer(experts, top_k, held, DTYPES[dtype])
    y, aux = m.apply(p, u)
    assert _counter("fallback.nn.moe") == 0
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(grouped_matmul, "available", lambda interpret=False: False)
        y_loop, aux_loop = m.apply(p, u)
    assert _counter("fallback.nn.moe") == 1
    _, source, first_row, blocks, _ = m._layout(aux["chosen"])
    block_expert, used = grouped_matmul.block_map(blocks, source.shape[0] // ROWS)
    w3 = [p["experts"][name] for name in ("w_gate", "w_up", "w_down")]
    ys = _rows_of(_KERNEL(u, source, *w3, block_expert, used, ROWS, interpret=True), D, u.dtype)
    in_use = int(used[0]) * ROWS
    assert np.array_equal(np.asarray(ys[:in_use], np.float32),
                          np.asarray(m._loop(p["experts"], u, source, first_row, blocks)[:in_use], np.float32))
    y32, y_loop32 = np.asarray(y, np.float32), np.asarray(y_loop, np.float32)
    assert gap(y32, y_loop32) < (1e-6 if dtype == "float32" else 1e-4)
    assert np.mean(y32 != y_loop32) < (1.0 if dtype == "float32" else 1e-2)
    assert np.array_equal(np.asarray(aux["load"]), np.asarray(aux_loop["load"]))
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    for which in ("xing4", "trinity"):
        want, chosen = _reference(which, p32, u.astype(jnp.float32), experts, top_k, held)
        assert np.array_equal(np.asarray(aux["chosen"]), np.asarray(chosen))
        assert gap(y, want) < (1e-5 if dtype == "float32" else 1.5e-2)


@pytest.mark.parametrize("load,held", [
    (load, held) for load in ("skewed", "one_expert", "empty_experts", "share_not_chosen")
    for held in (None, (4, 4)) if (load, held) != ("share_not_chosen", None)  # the whole is always chosen
], ids=lambda v: "whole" if v is None else "share" if isinstance(v, tuple) else v)
def test_no_load_drops_a_token_or_reads_an_unwritten_row(interpreted, load, held):
    """Whatever the imbalance, every token's experts are multiplied (the reference has no
    capacity), and the rows the kernel never wrote, NaN here, reach nothing. With a held
    share that no token chose the layer's output is exactly the shared expert's."""
    m, p, u = _layer(8, 2, held, jnp.float32, load)
    y, aux = m.apply(p, u)
    load_held = np.asarray(aux["load"])
    first, count = held or (0, 8)
    chosen = np.asarray(aux["chosen"])
    assert np.array_equal(load_held, np.bincount(chosen.reshape(-1), minlength=8)[first:first + count])
    if load == "one_expert":
        assert load_held[0] == T and load_held[1:].sum() == (T if held is None else 0)
    if load == "empty_experts":
        assert not load_held[::2].any() and load_held[1::2].all()
    assert bool(jnp.isfinite(y).all())
    if load == "share_not_chosen":
        assert not load_held.any()
        assert np.array_equal(np.asarray(y), np.asarray(m.shared.apply(p["shared"], u)))
    want, _ = _reference("trinity", p, u, 8, 2, held)
    assert gap(y, want) < 1e-5


def test_a_block_past_the_used_count_is_left_alone():
    """The map repeats the last used block's expert past the used count, and the kernel's
    output there is not a product: whatever the unused blocks' sources name, nothing of
    them comes out."""
    blocks = jnp.asarray([2, 0, 1, 0], jnp.int32)
    expert, used = grouped_matmul.block_map(blocks, 6)
    assert np.array_equal(np.asarray(expert), [0, 0, 2, 2, 2, 2]) and int(used[0]) == 3
    expert0, used0 = grouped_matmul.block_map(jnp.zeros((4,), jnp.int32), 6)
    assert int(used0[0]) == 0 and len(set(np.asarray(expert0).tolist())) == 1  # one expert, fetched once
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k1, (40, D), jnp.float32)
    source = jax.random.randint(k5, (6 * ROWS,), 0, 40, jnp.int32)
    w = [jax.random.normal(k, s, jnp.float32) * 0.1
         for k, s in ((k2, (4, D, H)), (k3, (4, D, H)), (k4, (4, H, D)))]
    def rows_of(words):
        return _rows_of(words, D, jnp.float32)

    ys = rows_of(_KERNEL(x, source, *w, expert, used, ROWS, interpret=True))
    for j, e in enumerate([0, 0, 2]):
        rows = slice(j * ROWS, (j + 1) * ROWS)
        want = ht.nn.modules.gated_silu(x[source[rows]], w[0][e], w[1][e], w[2][e])
        assert gap(ys[rows], want) < 1e-6
    # what the unused blocks' sources name does not matter (they name a token, as padding does)
    moved = rows_of(_KERNEL(x, source.at[3 * ROWS:].set(7), *w, expert, used, ROWS, interpret=True))
    assert np.array_equal(np.asarray(ys[:3 * ROWS]), np.asarray(moved[:3 * ROWS]))
    # a step whose rows are walked in a rolled loop of chunks (what the rule does at 512 rows)
    assert grouped_matmul._row_chunk(ROWS, D, H, 4) == ROWS
    assert grouped_matmul._row_chunk(512, 2048, 1024, 2) == 128 == grouped_matmul._row_chunk(512, 3584, 1024, 2)
    assert grouped_matmul._row_chunk(512, 8192, 2048, 2) == 64  # a body past the size measured good
    halves = rows_of(grouped_matmul._grouped_pallas(x, source, *w, expert, used, block_rows=ROWS,
                                                    sub=ROWS // 2, interpret=True))
    assert np.array_equal(np.asarray(ys[:3 * ROWS]), np.asarray(halves[:3 * ROWS]))


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_tokens_survive_the_packing(dtype):
    """``_words`` lays a token out as whole (8, 128) tiles of 32-bit words, bfloat16 columns ``c``
    and ``c + d / 2`` in one word: unpacked as the kernel does, every element comes back."""
    d = 3 * 256  # 3 lane tiles of bfloat16 pairs, 6 of float32: padded to 8
    x = jax.random.normal(jax.random.key(3), (5, d), jnp.float32).astype(DTYPES[dtype])
    tiles, padded = grouped_matmul._token_tiles(d, x.dtype.itemsize)
    assert (tiles, padded) == ((3, 8) if dtype == "bfloat16" else (6, 8))
    words = grouped_matmul._words(x).reshape(5, padded * 128)[:, :tiles * 128]
    if dtype == "float32":
        back = jax.lax.bitcast_convert_type(words, jnp.float32)
    else:
        low = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
        high = jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), jnp.float32)
        back = jnp.concatenate([low, high], axis=1).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(back, np.float32), np.asarray(x, np.float32))
    # the grouped kernel's epilogue packs a float32 product, rounded once, into the same words
    packed = grouped_matmul._pack(x.astype(jnp.float32), x.dtype)
    assert np.array_equal(np.asarray(packed), np.asarray(words))


def _jnp_combine(ys, slot, w):
    """``MoE``'s combine where the gate declines: rank-major pairs, a select, XLA's sum."""
    (t, k), rows = slot.shape, ys.shape[0]
    flat = slot.T.reshape(-1)
    held = flat < rows
    picked = jnp.where(held[:, None], ys[jnp.where(held, flat, 0)], 0).reshape(k, t, -1)
    return jnp.sum(picked.astype(jnp.float32) * w.T[:, :, None], axis=0)


def _choices(case, t):
    """``(layer, chosen (t, k))`` of a combine case: the layer states who is held, the
    choices are drawn or set so that the case is what its name says."""
    rng = np.random.default_rng(len(case))

    def draw(n, k):
        return np.argsort(rng.random((t, n)), axis=1)[:, :k].astype(np.int32)

    if case == "all_held_64_top4":  # Xing4's layer: whole blocks of held pairs, no branch
        return ht.nn.MoE(D, H, 64, 4, 0, 1.0, None, ROWS), draw(64, 4)
    if case == "all_held_128_top8":  # Trinity's
        return ht.nn.MoE(D, H, 128, 8, 0, 1.0, None, ROWS), draw(128, 8)
    if case == "all_held_ragged":  # tokens no whole number of blocks: the branching form
        return ht.nn.MoE(D, H, 16, 4, 0, 1.0, None, ROWS), draw(16, 4)
    if case == "quarter_held_group_limit":  # Ling's cut down: 64 in 8 groups, 4 stay, 16 held
        m = ht.nn.MoE(D, H, 64, 8, 0, 1.0, (0, 16), ROWS, n_group=8, topk_group=4)
        groups = np.argsort(rng.random((t, 8)), axis=1)[:, :4]
        inside = np.argsort(rng.random((t, 32)), axis=1)[:, :8]
        return m, (groups[np.arange(t)[:, None], inside // 8] * 8 + inside % 8).astype(np.int32)
    if case == "nobody_held":
        return ht.nn.MoE(D, H, 32, 4, 0, 1.0, (24, 8), ROWS), draw(24, 4)
    assert case == "all_beside_none"  # tokens alternate: every pair held, no pair held
    chosen = np.where((np.arange(t) % 2 == 0)[:, None], 8 + draw(8, 4), draw(8, 4))
    return ht.nn.MoE(D, H, 32, 4, 0, 1.0, (8, 8), ROWS), chosen.astype(np.int32)


COMBINES = {"all_held_64_top4": 512, "all_held_128_top8": 256, "all_held_ragged": 200,
            "quarter_held_group_limit": 200, "nobody_held": 96, "all_beside_none": 300}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("case", list(COMBINES))
def test_the_combine_kernel_equals_the_jnp_combine(case, dtype):
    """``moe_combine_fwd`` interpreted against the ``jnp`` combine over one buffer, laid out by the
    layer's own ``_layout``; every row no pair points at (unused blocks, the padding rows of a
    block in use) and every sublane row that pads a buffer row is NaN, and the result is finite:
    a pair held elsewhere is selected to 0 and never multiplied, and nothing else is read. The
    kernel sums rank by rank in float32; XLA takes its own order (and a CPU contracts a product
    into the sum), so the two agree within one float32 ulp of the sum of the terms' sizes, and
    the kernel's own stated order, redone in numpy, within the same."""
    t, d = COMBINES[case], 2 * D
    m, chosen = _choices(case, t)
    k, kind = m.top_k, DTYPES[dtype]
    slot, source, _, _, load = m._layout(jnp.asarray(chosen))
    slot, rows = slot.reshape(t, k), source.shape[0]
    held = np.asarray(slot) < rows
    assert int(load.sum()) == held.sum()
    if case.startswith("all_held"):
        assert held.all()
    if case == "nobody_held":
        assert not held.any()
    if case == "all_beside_none":
        assert held[::2].all() and not held[1::2].any()
    if case == "quarter_held_group_limit":
        assert 0.1 < held.mean() < 0.4 and held.all(axis=1).sum() == 0
    k1, k2 = jax.random.split(jax.random.key(t))
    pointed = jnp.zeros((rows,), bool).at[slot.reshape(-1)].set(True, mode="drop")
    ys = jnp.where(pointed[:, None], jax.random.normal(k1, (rows, d), jnp.float32), jnp.nan).astype(kind)
    w = jax.random.uniform(k2, (t, k), jnp.float32, 0.05, 1.0)
    tiles, padded = grouped_matmul._token_tiles(d, ys.dtype.itemsize)
    words = grouped_matmul._words(ys)
    words = jnp.where((jnp.arange(words.shape[0]) % padded < tiles)[:, None], words, _nan_word(kind))
    assert np.array_equal(np.asarray(_rows_of(words, d, kind), np.float32), np.asarray(ys, np.float32),
                          equal_nan=True)
    got = np.asarray(_COMBINE(words, slot, w, d, kind, all_held=m.count == m.n_experts, interpret=True))
    assert got.shape == (t, d) and got.dtype == np.float32 and np.isfinite(got).all()
    picked = np.where(held[:, :, None], np.asarray(ys, np.float32)[np.where(held, slot, 0)], 0)
    terms = picked * np.asarray(w)[:, :, None]
    room = (k - 1) * np.finfo(np.float32).eps * np.abs(terms).sum(axis=1)
    assert (np.abs(got - np.asarray(_jnp_combine(ys, slot, w))) <= room).all()
    in_rank_order = terms[:, 0]
    for j in range(1, k):
        in_rank_order = in_rank_order + terms[:, j]
    assert (np.abs(got - in_rank_order) <= room).all()
    if not held.any():
        assert not got.any()


REASONS = {
    "float16": (dict(dtype=jnp.float16), "streams float16"),
    "mixed": (dict(w_dtype=jnp.float32, down_dtype=jnp.bfloat16), "streams"),
    "width": (dict(d=128), "tiles: d=128"),
    "hidden": (dict(h=192), "h=192"),
    "block_rows": (dict(block_rows=8), "block_rows=8"),
    "ragged": (dict(rows=ROWS * 3, block_rows=32), "divide rows"),
    "source_tile": (dict(rows=96 * 4, block_rows=96), "1024"),
    # the two gathered blocks alone pass the cap, whatever slab of the hidden width a step takes
    "vmem": (dict(d=16384, h=4096, block_rows=1024, rows=2048), "no slab of its hidden width fits"),
    "top_k": (dict(top_k=256), "combine: top_k=256"),
    "rows": (dict(rows=2**21), "pass 32 bits"),
}


@pytest.mark.parametrize("case", list(REASONS))
def test_the_gate_says_why(case):
    kw, said = REASONS[case]
    dtype = kw.get("dtype", jnp.bfloat16)
    d, h, rows, block_rows = kw.get("d", D), kw.get("h", H), kw.get("rows", 4 * ROWS), kw.get("block_rows", ROWS)
    x = jax.ShapeDtypeStruct((T, d), dtype)
    w_gate = jax.ShapeDtypeStruct((4, d, h), kw.get("w_dtype", dtype))
    w_down = jax.ShapeDtypeStruct((4, h, d), kw.get("down_dtype", kw.get("w_dtype", dtype)))
    assert said in grouped_matmul.decline_reason(x, rows, w_gate, w_down, block_rows, kw.get("top_k", 2))


@pytest.mark.parametrize("d,h,e,k,b,hs,hs32,combine_mib", [
    (2048, 1024, 128, 8, 512, 1024, 1024, 32), (3584, 1024, 64, 4, 512, 1024, 512, 32),
    (2560, 768, 128, 8, 128, 768, 768, 32), (7168, 2048, 16, 8, 256, 512, 256, 48)],
    ids=["trinity", "xing4", "ling", "dsv32"])
def test_the_gate_admits_the_cells(d, h, e, k, b, hs, hs32, combine_mib):
    """A slab of every expert resident in both pipeline buffers, under the cap, at the four widths
    the benchmark runs: the whole expert where it fits (Trinity, Xing4, Ling), a quarter of
    DeepSeek-V3.2's hidden width at its block rows; and the combine's two gathered blocks of
    1,024 pairs beside its output. Float32 streams are admitted in slabs too: of Xing4's width in
    halves (declined before the slab plan), of DeepSeek's in eighths."""
    from heat_tpu.nn.deepseek_v32 import BLOCK_ROWS

    assert d != 7168 or b == BLOCK_ROWS
    x = jax.ShapeDtypeStruct((32768, d), jnp.bfloat16)
    w_gate, w_down = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in ((e, d, h), (e, h, d)))
    assert grouped_matmul.decline_reason(x, 4096, w_gate, w_down, b, k) is None
    assert grouped_matmul._slab(d, h, b, 2, 2) == hs
    need = grouped_matmul._footprint(d, h, b, 2, 2, hs)
    assert 2 * 3 * d * hs * 2 < need < grouped_matmul._VMEM_CAP - grouped_matmul._VMEM_MARGIN
    assert grouped_matmul._combine_blocks(k) == (1024 // k, 128 // k)
    rows_held = 2 * 1024 * grouped_matmul._token_tiles(d, 2)[1] * 512
    assert rows_held < grouped_matmul._combine_footprint(d, k, 2) < combine_mib * 2**20
    x32, g32, d32 = (jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in (x, w_gate, w_down))
    assert grouped_matmul.decline_reason(x32, 4096, g32, d32, b, k) is None
    assert grouped_matmul._slab(d, h, b, 4, 4) == hs32


@pytest.fixture
def slabs_forced(monkeypatch):
    """``slabs(d, h, block_rows, size, n)`` cuts :data:`_VMEM_CAP` to what a step walking ``h`` in
    ``n`` slabs holds, so that the gate picks that walk at a toy shape. The jitted wrapper's traces
    read the cap when traced: they are dropped before and after."""
    grouped_matmul._grouped_pallas.clear_cache()

    def slabs(d, h, block_rows, size, n):
        need = grouped_matmul._footprint(d, h, block_rows, size, size, h // n)
        monkeypatch.setattr(grouped_matmul, "_VMEM_CAP", need + grouped_matmul._VMEM_MARGIN)
        assert grouped_matmul._slab(d, h, block_rows, size, size) == h // n

    yield slabs
    grouped_matmul._grouped_pallas.clear_cache()


SLAB_MAPS = {"expert_change_and_unused_tail": [2, 0, 1, 0], "one_used_block": [0, 1, 0, 0]}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("case", list(SLAB_MAPS))
def test_the_slab_walk_equals_gated_silu(slabs_forced, case, n, dtype):
    """Where a whole expert does not fit, a step walks the hidden width in ``n`` slabs (grid axis
    1) and sums the down product in float32 slab by slab before its one rounding: every used
    block equals ``gated_silu`` of its rows within float32's rounding of that sum (bfloat16: the
    same but for an element on a rounding edge), across an expert change between blocks and for a
    single used block; the unused blocks past it repeat the last used block's last slab and write
    nothing that a used block's rows depend on. The trace counts ``kernels.gmm.fwd.slabs``."""
    h, kind = 4 * H, DTYPES[dtype]
    slabs_forced(D, h, ROWS, jnp.dtype(kind).itemsize, n)
    expert, used = grouped_matmul.block_map(jnp.asarray(SLAB_MAPS[case], jnp.int32), 6)
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.key(n), 5)
    x = jax.random.normal(k1, (40, D), jnp.float32).astype(kind)
    source = jax.random.randint(k5, (6 * ROWS,), 0, 40, jnp.int32)
    w = [(jax.random.normal(k, s, jnp.float32) * 0.1).astype(kind)
         for k, s in ((k2, (4, D, h)), (k3, (4, D, h)), (k4, (4, h, D)))]
    was_on = ht.diagnostics.enabled()
    ht.diagnostics.enable()
    ht.diagnostics.reset()
    try:
        ys = _rows_of(_KERNEL(x, source, *w, expert, used, ROWS, interpret=True), D, kind)
        assert _counter("kernels.gmm.fwd") == 1 and _counter("kernels.gmm.fwd.slabs") == 1
    finally:
        ht.diagnostics.reset()
        if not was_on:
            ht.diagnostics.disable()
    in_use = int(used[0])
    assert in_use == sum(SLAB_MAPS[case])
    for j, e in enumerate(np.asarray(expert)[:in_use]):
        rows = slice(j * ROWS, (j + 1) * ROWS)
        want = ht.nn.modules.gated_silu(x[source[rows]], w[0][e], w[1][e], w[2][e])
        if dtype == "float32":
            assert gap(ys[rows], want) < 1e-6
        else:
            assert gap(ys[rows], want) < 1e-3 and np.mean(np.asarray(ys[rows] != want)) < 1e-2
    moved = _rows_of(_KERNEL(x, source.at[in_use * ROWS:].set(7), *w, expert, used, ROWS,
                             interpret=True), D, kind)
    assert np.array_equal(np.asarray(ys[:in_use * ROWS], np.float32),
                          np.asarray(moved[:in_use * ROWS], np.float32))


def test_the_counters_count_traces_not_calls(interpreted):
    """``kernels.gmm.fwd`` and ``kernels.gmm.combine`` count a trace of the path that took the two
    kernels, ``fallback.nn.moe`` a trace that did not, with its reason; a warmed call counts none."""
    _, p, u = _layer(8, 2, None, jnp.bfloat16)
    m = ht.nn.MoE(D, H, 8, 2, 1, 2.0, None, 2 * ROWS, dtype=jnp.bfloat16)  # this test's own shape
    f = jax.jit(lambda p, u: m.apply(p, u)[0])
    f(p, u)
    assert _counter("kernels.gmm.fwd") >= 1 and _counter("fallback.nn.moe") == 0
    assert _counter("kernels.gmm.combine") >= 1
    before = _counter("kernels.gmm.fwd"), _counter("kernels.gmm.combine")
    f(p, u)
    assert (_counter("kernels.gmm.fwd"), _counter("kernels.gmm.combine")) == before
    odd = ht.nn.MoE(D, H, 8, 2, 1, 2.0, None, 8, dtype=jnp.bfloat16)  # half a sublane tile
    jax.jit(lambda p, u: odd.apply(p, u)[0])(p, u)
    assert _counter("fallback.nn.moe") == 1  # and neither kernel: the buffer's layout ties them
    assert (_counter("kernels.gmm.fwd"), _counter("kernels.gmm.combine")) == before
    event = ht.diagnostics.report()["fallback_events"][-1]
    assert event["site"] == "nn.moe" and "block_rows=8" in event["reason"]


def test_without_a_tpu_the_loop_runs_and_says_so():
    was_on = ht.diagnostics.enabled()
    ht.diagnostics.enable()
    ht.diagnostics.reset()
    try:
        m, p, u = _layer(8, 2, None, jnp.float32)
        y, _ = m.apply(p, u)
        assert _counter("kernels.gmm.fwd") == 0 and _counter("fallback.nn.moe") == 1
        assert _counter("kernels.gmm.combine") == 0
        assert "backend cpu" in ht.diagnostics.report()["fallback_events"][-1]["reason"]
        assert gap(y, _reference("xing4", p, u, 8, 2, None)[0]) < 1e-5
    finally:
        ht.diagnostics.reset()
        if not was_on:
            ht.diagnostics.disable()
