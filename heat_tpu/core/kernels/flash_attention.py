"""Flash-attention Pallas kernels (TPU).

The XLA blockwise path in ``heat_tpu/nn/attention.py`` materialises the (T, T)
score matrix in HBM — at T=4096, B·H=128 that is ~8 GB of f32 traffic and the op
runs HBM-bound at a few TFLOP/s. The forward kernel streams k/v through VMEM with
the standard online-softmax recurrence: for each query block the k/v blocks are
visited sequentially, the score tile lives only in VMEM, and the rescaled output
accumulator is written to HBM once. Causal masking skips whole k-blocks above the
diagonal (the pair list simply does not hold them, so the causal kernel does ~half
the steps instead of masking all of them).

**Geometry of the forward (PR 30).** A grid step takes one ``(bq, bk)`` block pair
(what the DMA moves) and walks it as ``(bq / br) x (bk / bs)`` sub-tiles in ONE
straight-line region: ``s = q_r·k_cᵀ``, the online-softmax update of the row chunk's
state, ``p·v_c``. Row chunks share no state and a sub-tile's ``q·k`` does not wait for
its predecessor's ``exp``, so Mosaic's scheduler issues one sub-tile's contractions
under another's VPU pass: at ``(1024, 1024)`` blocks with ``(256, 512)`` sub-tiles the
static schedule of the plain step is MXU-bound from its first bundle to its last. The
softmax state is kept lane-dense, ``(bq, 128)`` with every lane the row's value, so no
step pays a lane broadcast. ``br = bq, bs = bk`` is the serial form (the MXU phase and
the VPU phase of a step one after the other); :func:`_sub_tiles` chooses from the
block shape, no switch. **Bound and share** (v5e, d_qk 192 / d_v 128, bf16, T 32,768,
32 heads; ``doc/source/flash_attention_perf.rst``): the kernel is compute-bound (K/V
re-reads hide behind the double buffer), and the MXU takes the 192-wide contraction as
two 128-deep passes, so a step does the work of widths 256 + 128 where its FLOP count
has 192 + 128: 83% of the MXU peak is the ceiling of ``mla_flash_roofline_share`` at
these widths. Measured (PR 30, device trace): 77.3 ms a call, 142 TFLOP/s, 72% of 197,
from 113.2 ms and 49%.

**Window and grouped heads (PR 31, the forward only).** Under a ``window`` a row sweep lists
only the key blocks that meet the band ``i - window < j <= i`` (at 32,768 tokens, a window
of 2,048 and (1024, 1024) blocks: 93 steps a head for the causal 528); the blocks that
straddle the diagonal or the band's lower edge are masked, sub-tile by sub-tile, the same
forward body. k and v may have fewer heads than q: grid row ``b`` reads key/value head
``b // (Hq / Hkv)`` through the block index map, and nothing is repeated in HBM (on the
chip the call takes the same time as over repeated heads). Measured at d = d_v = 128,
32 / 4 heads, T 32,768 (device trace inside ``trinity-score-32k``): causal
(``gqa_flash_fwd``) 54.1 ms a call, 163 TFLOP/s, 82% of 197; band (``swa_flash_fwd``)
10.2 ms, 53% by the band's own pairs: two of a sweep's three steps are masked steps,
whose sub-tiles run in regions of their own.

**The latent operand form (the forward only).** Latent attention's q, its
``[k_nope | v]`` projection and its one rope key enter as the projections leave them
(:func:`flash_latent`): k_nope and v are two lane blocks of one array, the rope key one
block for every head, and a sub-tile's key and query are put together in VMEM for the same
192-deep product; a row sweep's first step turns the query's rope lanes itself. Nothing is
concatenated, repeated or sliced in HBM, and no LSE is written. Mosaic's schedule for a
described v5e puts the plain step at 6,313 bundles against the concatenated call's 6,423
(under a packed mask; 6,050 against 6,120 without). The turn costs ~2% of the kernel on the
chip; turned in XLA instead and read as a block of their own, the rope lanes cost more
there than they saved here (~10 ms a DeepSeek-V3.2 layer against ~4).

Backward: the ``jax.custom_vjp`` backward is also Pallas — the forward saves the
(O, LSE) residuals, ``_dq_kernel`` streams k/v per query block and ``_dkv_kernel``
streams q/dO per key block, each recomputing its probability tile from the LSE
(the standard flash backward). Neither direction materialises the (T, T) matrix
in HBM.

No reference counterpart: the reference has no attention at all (SURVEY §2.4);
this is TPU-first machinery for the long-context story.
"""

from __future__ import annotations

import functools
import os
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import diagnostics
from .sparse_index import WORD_KEYS, mask_words

__all__ = ["flash_attention", "flash_attention_reference", "flash_forward", "flash_latent",
           "forward_blocks", "latent_blocks"]

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANES = 128

# Forward block preference, per input itemsize, largest first: larger bq halves the
# K/V re-reads and the grid steps, larger bk the accumulator rescales a score element.
# Since the step walks its block in sub-tiles, the live f32 tiles no longer grow with
# the block, and (1024, 1024) fits Mosaic's default scope at every width the gates
# admit. Shapes that only divide 512 fall back to 512-blocks rather than losing the
# flash path entirely.
_FWD_BLOCK_PREFS = {
    2: ((1024, 1024), (512, 1024), (1024, 512), (512, 512)),
    4: ((512, 1024), (512, 512)),
}
# Sub-tile of a grid step: query rows of an independent chunk, keys of one pass of the
# online-softmax recurrence. Read off Mosaic's static schedule at (192, 128) bf16: 256
# rows keep a key sub-tile's weights in the MXU for 16 pushes of operand rows; 512 keys
# halve the cross-lane reductions of 256 (each sub-tile pays one row-max and one
# row-sum per 8 rows on the XLU).
_SUB_ROWS = 256
_SUB_KEYS = 512
_BWD_BQ = 512
_BWD_BK = 512
# scalar-prefetch schedule bound: the flattened pair list is O((T/b)²) int32
# entries shipped to SMEM — cap it well below SMEM capacity
_MAX_PAIRS = 8192
# what a grid step may hold by the footprint model, of Mosaic's default scope of 16 MiB:
# the quarter left over is for what the model cannot see (at blocks of 2,048 Mosaic
# asks for a tenth more than the model counts; at the preferred blocks for less)
_VMEM_BUDGET = 12 * 2**20


def _env_vmem_limit():
    """HEAT_TPU_FLASH_VMEM_LIMIT in bytes, or None when unset, malformed, or not
    positive (graceful degradation, like _env_blocks — a bad value must not take
    down every attention dispatch)."""

    raw = os.environ.get("HEAT_TPU_FLASH_VMEM_LIMIT")  # ht: ignore[trace-env-read] -- documented trace-time tuning knob (see docstring): kernel block geometry is necessarily a compile-time constant; re-tune in a fresh process
    if not raw:
        return None
    try:
        v = int(raw.strip())
    except ValueError:
        return None
    return v if v > 0 else None


def _vmem_budget() -> int:
    """What the gates let a grid step hold: the hand-tuning knob when set (the same
    value _compiler_params forwards to Mosaic, so block-size experiments that lift the
    VMEM budget actually reach the flash path), else :data:`_VMEM_BUDGET`."""
    return _env_vmem_limit() or _VMEM_BUDGET


def _compiler_params(pltpu):
    """Mosaic params shared by all three kernels: the batch·head grid dim is
    embarrassingly parallel (no state crosses it), the pair dim is a sequential
    sweep (softmax/accumulator state carries across it). Marking them lets the
    compiler reorder/parallelise batch steps instead of assuming a serial grid.
    ``HEAT_TPU_FLASH_VMEM_LIMIT`` (bytes) lifts the VMEM budget for block-size
    experiments on real hardware."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_env_vmem_limit(),
    )


def _env_blocks(default_bq: int, default_bk: int):
    """Block-size override for on-chip tuning: HEAT_TPU_FLASH_BLOCKS=\"bq,bk\".

    Read at TRACE time: jit caches by shape/dtype, so changing the env between
    same-shape calls in one process reuses the first compilation — run each
    config in a fresh process (or clear jax caches) when sweeping."""

    spec = os.environ.get("HEAT_TPU_FLASH_BLOCKS")  # ht: ignore[trace-env-read] -- documented trace-time tuning knob (see docstring): kernel block geometry is necessarily a compile-time constant; re-tune in a fresh process
    if not spec:
        return default_bq, default_bk
    try:
        bq, bk = (int(x) for x in spec.split(","))
    except ValueError:
        return default_bq, default_bk
    if bq <= 0 or bk <= 0:
        return default_bq, default_bk
    return bq, bk


def _sub_tiles(bq: int, bk: int) -> tuple:
    """``(br, bs)``: the sub-tile in which a grid step walks its ``(bq, bk)`` block.
    A block no larger than the sub-tile, or one it does not divide, is walked whole
    (the serial form)."""
    br = _SUB_ROWS if bq > _SUB_ROWS and bq % _SUB_ROWS == 0 else bq
    bs = _SUB_KEYS if bk > _SUB_KEYS and bk % _SUB_KEYS == 0 else bk
    return br, bs


def _fwd_footprint(bq: int, bk: int, d: int, dv: int, itemsize: int,
                   with_bias: bool = False, with_mask: bool = False, turned: int = 0) -> int:
    """Bytes of VMEM a forward grid step holds at blocks ``(bq, bk)``: the one
    footprint model, which :func:`_fits` and :func:`forward_blocks` both gate on.
    Counted: the q / k / v / out blocks double-buffered (last dimension padded to 128
    lanes), the double-buffered LSE block and the running max / sum (one value a row,
    128 lanes each), the f32 accumulator of v's width, a streamed f32 bias block
    double-buffered, a streamed tile of mask words (``(bq, 128)`` int32) likewise, and the
    live tiles of the step's sub-tiles: f32 scores, f32
    probabilities and the probabilities in v's type, twice where the step has more
    than one sub-tile (one under the VPU while the next is under the MXU). ``turned``: the
    latent form turns that many rope lanes of its query block in the kernel, from a
    double-buffered f32 ``(2, bq, turned)`` block of cosines and sines into a ``(bq,
    turned)`` copy in q's type. Its key arrives as two blocks whose lanes add up to ``d``
    and is put together as a live value like q's; it writes no LSE, which is still counted.
    Against the
    least ``vmem_limit_bytes`` Mosaic accepts (AOT, v5e, PR 30) the model reads high at
    the preferred blocks (8.0 MiB for 5.0 at (1024, 1024), 192 / 128, bf16; 15.0 for
    14.4 with a bias) and low beyond them (13.5 for 14.9 at (2048, 2048))."""
    br, bs = _sub_tiles(bq, bk)

    def pad(n):
        return -(-n // _LANES) * _LANES

    blocks = 2 * itemsize * (bq * pad(d) + bk * pad(d) + bk * pad(dv) + bq * pad(dv))
    state = 4 * bq * (pad(dv) + 4 * _LANES)
    tiles = (8 + itemsize) * br * bs * (1 if (br, bs) == (bq, bk) else 2)
    bias = 8 * bq * bk if with_bias else 0
    words = 8 * bq * _LANES if with_mask else 0
    turn = (16 + itemsize) * bq * pad(turned) if turned else 0
    return blocks + state + tiles + bias + words + turn


def _fwd_blocks(dtype, tq: int, tk: int, with_bias: bool = False) -> tuple:
    """Largest preferred (bq, bk) that tiles (tq, tk) evenly, else the smallest
    preference (whose divisibility _fits re-checks and may reject). A streamed
    bias adds a double-buffered f32 (bq, bk) block, so biased bf16 runs use the
    smaller f32 tile preferences."""
    size = 4 if with_bias else jnp.dtype(dtype).itemsize
    prefs = _FWD_BLOCK_PREFS.get(size, ((512, 512),))
    ebq, ebk = _env_blocks(0, 0)
    if ebq and tq % ebq == 0 and tk % ebk == 0:  # on-chip tuning override
        return ebq, ebk
    for bq, bk in prefs:
        if tq % bq == 0 and tk % bk == 0:
            return bq, bk
    return prefs[-1]


def flash_attention_reference(q, k, v, causal: bool = False, scale=None):
    """Pure-jnp exact attention (f32 accumulation) — the parity oracle."""
    d = q.shape[-1]
    s = (1.0 / math.sqrt(d)) if scale is None else scale
    scores = jnp.einsum("...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32)
    scores = scores * jnp.float32(s)
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        scores = jnp.where(mask, scores, _NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("...qk,...kd->...qd", p, v, preferred_element_type=jnp.float32)
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _lanes(x, n: int):
    """A lane-dense ``(rows, 128)`` state value (every lane the row's value) at ``n``
    lanes: whole vregs side by side, or a prefix of one."""
    if n % _LANES == 0:
        return x if n == _LANES else jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _kernel(im_ref, jm_ref, flags_ref, q_ref, k_ref, v_ref, *refs,
            scale: float, bq: int, bk: int, br: int, bs: int, has_bias: bool = False,
            window=None, has_mask: bool = False, rope: int = 0, turn: bool = False):
    """One (q-block, k-block) pair of the online-softmax recurrence, walked as
    ``(bq / br) x (bk / bs)`` sub-tiles in one straight-line region.

    The grid is the *flattened list of contributing (i, j) pairs* (splash-style):
    for causal attention the blocks strictly above the diagonal are not idle grid
    steps — they simply aren't in the list, so the causal kernel really does half
    the steps. Scalar-prefetched maps give each step its (i, j); flags mark the
    first/last step of each q-row sweep (init / finalize) and whether the block
    straddles an edge of what a row may see: the diagonal or, under a ``window``
    (row ``i`` sees keys ``i - window < j <= i``), the band's lower edge. The steps that
    straddle neither, all but one a causal row, are ONE basic block: every sub-tile's
    two contractions and softmax pass lie in it with no branch between them, which is
    what lets the scheduler put the ``exp`` pass of one sub-tile under the contractions
    of the next. A straddling step has a region of its own: the same sub-tiles with the
    iota/where mask, each behind a scalar test that skips it when it lies wholly above
    the diagonal or wholly below the band.

    ``has_mask``: what a row may see differs row by row. A streamed tile of packed mask
    words (``sparse_index.pack_mask``'s layout: bit ``b`` of lane ``l`` is key ``128 b + l``
    of the tile's 4,096) decides every element of every step in place of the iota mask, a
    lane tile of keys a shift and an ``and``; the schedule is the causal one (the mask lies
    under the diagonal), so a diagonal step still skips its sub-tiles above it, and no
    other pair is dropped: none is known to be empty when the call is traced.

    ``rope`` (the latent form): q's last ``rope`` lanes are its rope part, ``k_ref`` holds a
    key's other lanes and ``kr_ref`` its ``rope`` lanes, one block for every head; a
    sub-tile's key is the two put side by side in VMEM, so the contraction is the one
    ``d``-deep product it always was. ``turn``: the query's rope lanes still need their
    rotary positions; the first step of a row sweep turns them once, 128 rows at a time, in
    float32 from the ``(2, bq, rope)`` block of ``[cos | cos]`` and ``[-sin | sin]`` with the
    halves swapped by an exact product on the MXU (no lane rotation), the products and sums
    of ``rotate_halves``, into a copy the sweep's sub-tiles read beside the block's other
    lanes. The latent form writes no LSE.

    Pallas double-buffers the k/v block DMA against compute because the kv pair
    index advances with the grid. MXU inputs stay in the input dtype (bf16 runs
    at full MXU rate — forcing f32 here quarters throughput); softmax state and
    the output accumulator are f32, the running max and sum lane-dense (bq, 128).
    """
    import jax.experimental.pallas as pl

    refs = list(refs)
    kr_ref = refs.pop(0) if rope else None
    cs_ref = refs.pop(0) if turn else None
    bias_ref = refs.pop(0) if has_bias else None
    mask_ref = refs.pop(0) if has_mask else None
    qr_ref = refs.pop() if turn else None
    lse_ref = None if rope else refs.pop(1)
    o_ref, acc_ref, m_ref, l_ref = refs
    dn = q_ref.shape[2] - rope  # the query's lanes that need no turning

    p = pl.program_id(1)
    dv = v_ref.shape[2]
    flags = flags_ref[p]
    is_first, is_last = flags & 1, flags & 2
    needs_mask = flags & (4 if window is None else 4 | 8)
    row0, col0 = im_ref[p] * bq, jm_ref[p] * bk
    # 16-bit operands are one MXU pass whatever the process-wide default says (Mosaic
    # refuses them at "highest"); float32 operands follow the caller's context
    precision = lax.Precision.DEFAULT if q_ref.dtype.itemsize < 4 else None

    @pl.when(is_first != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if turn:
            rows = _LANES if bq % _LANES == 0 else bq
            # [a | b] -> [b | a] on the MXU, exact (one 1 a column): no lane rotations
            i = lax.broadcasted_iota(jnp.int32, (rope, rope), 0)
            j = lax.broadcasted_iota(jnp.int32, (rope, rope), 1)
            swap = (i == (j + rope // 2) % rope).astype(q_ref.dtype)

            def _turn(n, carry):  # a chunk of rows at a time: its operands stay in registers
                chunk = pl.ds(pl.multiple_of(n * rows, rows), rows)
                x = q_ref[0, chunk, dn:]
                swapped = lax.dot_general(x, swap, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32, precision=precision)
                # [a cos - b sin | b cos + a sin], rotate_halves' products and sums
                qr_ref[chunk, :] = (x.astype(jnp.float32) * cs_ref[0, chunk, :]
                                    + swapped * cs_ref[1, chunk, :]).astype(qr_ref.dtype)
                return carry

            lax.fori_loop(0, bq // rows, _turn, 0)

    def _tile(r: int, c: int, masked: bool):
        rows, cols = pl.ds(r * br, br), pl.ds(c * bs, bs)
        vb = v_ref[0, cols, :]
        if turn:
            qt = jnp.concatenate([q_ref[0, rows, :dn], qr_ref[rows, :]], axis=1)
        else:
            qt = q_ref[0, rows, :]
        kt = k_ref[0, cols, :]
        if rope:
            kt = jnp.concatenate([kt, kr_ref[0, cols, :]], axis=1)
        s = lax.dot_general(
            qt, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale  # (br, bs) f32
        if has_bias:
            s = s + bias_ref[rows, cols]
        if has_mask:
            # the key block's place in its tile of words, then the sub-tile's, in lane tiles
            first = jm_ref[p] % (WORD_KEYS // bk) * (bk // _LANES) + c * (bs // _LANES)
            words = mask_ref[rows, :]
            stays = jnp.concatenate([(words >> (first + g)) & 1 for g in range(bs // _LANES)],
                                    axis=1)
            s = jnp.where(stays != 0, s, _NEG_INF)
        elif masked:
            ri = row0 + r * br + lax.broadcasted_iota(jnp.int32, (br, bs), 0)
            ci = col0 + c * bs + lax.broadcasted_iota(jnp.int32, (br, bs), 1)
            keep = ri >= ci
            if window is not None:
                keep &= ri - ci < window
            s = jnp.where(keep, s, _NEG_INF)
        m = m_ref[rows, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # a bias or a mask can hide a whole row of the block (all -inf): keep the exps
        # finite — the row's l stays 0 and its output finalizes to 0 like the dense path
        m_safe = jnp.maximum(m_new, _NEG_INF / 2) if has_bias or has_mask else m_new
        p_tile = jnp.exp(s - _lanes(m_safe, bs))
        corr = jnp.exp(m - m_safe)
        l_ref[rows, :] = l_ref[rows, :] * corr + jnp.sum(p_tile, axis=1, keepdims=True)
        # probabilities ride the MXU in the value dtype (standard flash practice;
        # p ∈ [0,1] so the bf16 round-off is bounded), accumulation stays f32
        acc_ref[rows, :] = acc_ref[rows, :] * _lanes(corr, dv) + lax.dot_general(
            p_tile.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        m_ref[rows, :] = m_new

    sub_tiles = [(r, c) for r in range(bq // br) for c in range(bk // bs)]

    @pl.when(needs_mask != 0)
    def _masked():
        for r, c in sub_tiles:
            # a sub-tile wholly above the diagonal reaches no row: skipped; so is one
            # wholly below the band (its last key is out of its first row's window)
            live = col0 + c * bs <= row0 + (r + 1) * br - 1
            if window is not None:
                live &= col0 + (c + 1) * bs - 1 > row0 + r * br - window
            pl.when(live)(functools.partial(_tile, r, c, True))

    @pl.when(needs_mask == 0)
    def _plain():
        for r, c in sub_tiles:
            _tile(r, c, False)

    @pl.when(is_last != 0)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # log-sum-exp residual for the backward pass: L = m + log(l); the clamp
            # keeps fully-masked rows finite so the backward's exp(s - L) is 0, not NaN
            lse_ref[0] = jnp.maximum(m_ref[:, :1], _NEG_INF / 2) + jnp.log(jnp.maximum(l, 1e-30))


def _kv_group(q, k, v) -> int:
    """Query heads per key/value head: 1, or ``Hq // Hkv`` where k and v have fewer heads
    on axis -3 and every other leading axis agrees."""
    if k.shape[:-2] == q.shape[:-2] == v.shape[:-2]:
        return 1
    if (q.ndim < 3 or k.ndim != q.ndim or k.shape[:-2] != v.shape[:-2]
            or k.shape[:-3] != q.shape[:-3] or q.shape[-3] % k.shape[-3]):
        raise ValueError(f"key/value heads {k.shape[:-2]} / {v.shape[:-2]} do not group the "
                         f"query's {q.shape[:-2]}")
    return q.shape[-3] // k.shape[-3]


def _pair_schedule(nq: int, nk: int, bq: int, bk: int, causal: bool, window=None):
    """Flattened (i, j) visit list + per-step flag bits (1=first of row sweep,
    2=last of row sweep, 4=diagonal-straddling → mask, 8=straddling the band's lower
    edge → mask). Causal keeps only blocks with any (row ≥ col); mask is needed only
    when the block's last col exceeds the block's first row. Under a ``window`` (causal
    only) a row sees ``row - window < col <= row``: a row sweep lists the key blocks
    that meet that band and skips the rest, and a block whose first col is at or under
    its last row's lower limit straddles the band's lower edge."""
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a window is a causal band of at least one key; got causal="
                         f"{causal}, window={window}")
    im, jm, flags = [], [], []
    for i in range(nq):
        row_lo, row_hi = i * bq, i * bq + bq - 1
        js = [
            j for j in range(nk)
            if (not causal or j * bk <= row_hi)
            and (window is None or j * bk + bk - 1 > row_lo - window)
        ]
        if not js:  # an unvisited output block would hold uninitialised memory
            raise ValueError(f"no key block meets the window of query rows {row_lo}..{row_hi}")
        for idx, j in enumerate(js):
            f = (1 if idx == 0 else 0) | (2 if idx == len(js) - 1 else 0)
            if causal and (j * bk + bk - 1 > row_lo):
                f |= 4
            if window is not None and j * bk <= row_hi - window:
                f |= 8
            im.append(i)
            jm.append(j)
            flags.append(f)
    return np.asarray(im, np.int32), np.asarray(jm, np.int32), np.asarray(flags, np.int32)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "bq", "bk", "interpret", "sub", "name", "window"),
)
def _flash_pallas(q, k, v, causal: bool, scale: float, bq: int, bk: int,
                  interpret: bool = False, bias=None, sub=None, name=None, window=None,
                  mask=None, k_rope=None, turns=None):
    """q, k: (..., T, d); v: (..., Tk, dv) with its own width (dv != d is the latent-
    attention case: 192 against 128). k and v may have fewer heads (axis -3) than q,
    ``Hq = rep * Hkv``: query head ``h`` reads key/value head ``h // rep`` through the
    block index map, and nothing is repeated in HBM. ``window`` (causal only): row ``i``
    sees keys ``i - window < j <= i``, and the blocks outside that band are not visited.
    ``mask`` (causal only): packed words ``(T, mask_words(Tk))`` int32, shared by all heads,
    of the keys each row sees, all of them at or under the row's own position.
    ``sub`` is the step's sub-tile ``(br, bs)``, by default what :func:`_sub_tiles`
    reads off the blocks. ``name`` names the Pallas call in a device trace.

    ``k_rope`` (the latent form, see :func:`flash_latent`): ``k`` is the ``[k_nope | v]``
    projection ``(..., H, Tk, dn + dv)`` that the kernel reads as two lane blocks, ``v`` is
    None, ``k_rope`` ``(..., Tk, dr)`` is the one rope key of all ``H`` heads and q is
    ``(..., H, T, dn + dr)``; ``turns`` ``(2, T, dr)`` float32, ``[cos | cos]`` and
    ``[-sin | sin]``, turns q's rope lanes in the kernel. No LSE is written: the second
    result is None."""
    import jax.experimental.pallas as pl  # ht: ignore[trace-lazy-import] -- pallas imports deferred so CPU-only processes never pay them; runs once per compile, imports nothing of heat_tpu
    from jax.experimental.pallas import tpu as pltpu  # ht: ignore[trace-lazy-import] -- pallas imports deferred so CPU-only processes never pay them; runs once per compile, imports nothing of heat_tpu

    with jax.enable_x64(False):
        *batch, tq, d = q.shape
        bh = math.prod(batch) if batch else 1
        dr = 0 if k_rope is None else k_rope.shape[-1]
        if dr:
            tk, heads = k.shape[-2], q.shape[-3]
            dn = d - dr
            dv = k.shape[-1] - dn
            rep = 1
            if (k.shape[:-1] != q.shape[:-1] or k_rope.shape != q.shape[:-3] + (tk, dr)
                    or dn % _LANES or dv % _LANES or dn % dv
                    or (turns is not None and (dr % 2 or turns.shape != (2, tq, dr)))):
                raise ValueError(f"the latent form takes q (..., H, T, dn + dr), [k_nope | v] "
                                 f"(..., H, T, dn + dv) and k_rope (..., T, dr), dn and dv "
                                 f"whole lane tiles; got {q.shape}, {k.shape}, {k_rope.shape}")
            qr = q.reshape(bh, tq, d)
            kr = k.reshape(bh, tk, dn + dv)
            rr = k_rope.reshape(bh // heads, tk, dr)
        else:
            tk, dv = k.shape[-2], v.shape[-1]
            rep = _kv_group(q, k, v)
            qr = q.reshape(bh, tq, d)
            kr = k.reshape(bh // rep, tk, d)
            vr = v.reshape(bh // rep, tk, dv)
        has_bias, has_mask = bias is not None, mask is not None
        br, bs = _sub_tiles(bq, bk) if sub is None else sub
        if has_mask and (not causal or window is not None or WORD_KEYS % bk or bs % _LANES
                         or mask.shape != (tq, mask_words(tk))):
            raise ValueError(f"a packed mask goes with the causal schedule, key blocks that "
                             f"divide {WORD_KEYS} and words ({tq}, mask_words({tk})); got "
                             f"causal={causal}, window={window}, bk={bk}, {mask.shape}")

        im, jm, flags = _pair_schedule(tq // bq, tk // bk, bq, bk, causal, window)
        if diagnostics._enabled:  # trace time only: which schedule this trace's steps take
            if has_mask:
                diagnostics.counter("kernels.dsa.flash")
            if dr:
                diagnostics.counter("kernels.flash.fwd.latent")
            diagnostics.counter(
                "kernels.flash.fwd." + ("serial" if (br, bs) == (bq, bk) else "overlapped"))
            # block pairs the schedule lists against all of them: what causal and band skip
            diagnostics.counter("kernels.flash.fwd.pairs_visited", len(im))
            diagnostics.counter("kernels.flash.fwd.pairs_dense", (tq // bq) * (tk // bk))

        def kv_map(b, p, im, jm, fl):  # grid row b is (batch, query head)
            return (b if rep == 1 else b // rep), jm[p], 0

        if dr:
            # k_nope and v are lane blocks 0 and dn / dv of the one projection; the rope key
            # is a block of its own, the same for every head of a batch row
            in_specs = [
                pl.BlockSpec((1, bq, d), lambda b, p, im, jm, fl: (b, im[p], 0)),
                pl.BlockSpec((1, bk, dn), kv_map),
                pl.BlockSpec((1, bk, dv), lambda b, p, im, jm, fl: (b, jm[p], dn // dv)),
                pl.BlockSpec((1, bk, dr), lambda b, p, im, jm, fl: (b // heads, jm[p], 0)),
            ]
            inputs = [qr, kr, kr, rr]
            if turns is not None:
                in_specs.append(pl.BlockSpec((2, bq, dr), lambda b, p, im, jm, fl: (0, im[p], 0)))
                inputs.append(turns.astype(jnp.float32))
        else:
            in_specs = [
                pl.BlockSpec((1, bq, d), lambda b, p, im, jm, fl: (b, im[p], 0)),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, dv), kv_map),
            ]
            inputs = [qr, kr, vr]
        if has_bias:
            # (Tq, Tk) additive bias, broadcast over batch/heads: one (bq, bk)
            # block streams per pair, like k/v
            in_specs.append(
                pl.BlockSpec((bq, bk), lambda b, p, im, jm, fl: (im[p], jm[p]))
            )
            inputs.append(bias.astype(jnp.float32))
        if has_mask:
            # one tile of words covers 4,096 keys: it is fetched anew every 4096 / bk steps
            in_specs.append(pl.BlockSpec(
                (bq, _LANES), lambda b, p, im, jm, fl: (im[p], jm[p] // (WORD_KEYS // bk))))
            inputs.append(mask)
        out_specs = [pl.BlockSpec((1, bq, dv), lambda b, p, im, jm, fl: (b, im[p], 0))]
        out_shape = [jax.ShapeDtypeStruct((bh, tq, dv), q.dtype)]
        if not dr:  # the LSE, for a backward; the latent form has none
            out_specs.append(pl.BlockSpec((1, bq, 1), lambda b, p, im, jm, fl: (b, im[p], 0)))
            out_shape.append(jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32))
        scratch = [
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ]
        if turns is not None:  # the sweep's query rope lanes, turned
            scratch.append(pltpu.VMEM((bq, dr), q.dtype))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(im)),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        out, *lse = pl.pallas_call(
            functools.partial(_kernel, scale=scale, bq=bq, bk=bk, br=br, bs=bs,
                              has_bias=has_bias, window=window, has_mask=has_mask,
                              rope=dr, turn=turns is not None),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=None if interpret else _compiler_params(pltpu),
            name=name,
        )(jnp.asarray(im), jnp.asarray(jm), jnp.asarray(flags), *inputs)
        return out.reshape(*batch, tq, dv), (lse[0].reshape(*batch, tq) if lse else None)


def _dq_kernel(im_ref, jm_ref, flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               dd_ref, *refs, scale: float, bq: int, bk: int, has_bias: bool = False):
    """dq_i = Σ_j dS_ij · k_j · scale with dS = P ∘ (dO·Vᵀ − D).

    Streams k/v blocks over the same flattened (i, j) pair grid as the forward;
    the dq accumulator lives in VMEM scratch across each row sweep, so only
    O(bq·bk) is resident regardless of T."""
    import jax.experimental.pallas as pl

    if has_bias:
        bias_ref, dq_ref, acc_ref = refs
    else:
        dq_ref, acc_ref = refs

    p = pl.program_id(1)
    flags = flags_ref[p]
    is_first, is_last, needs_mask = flags & 1, flags & 2, flags & 4

    @pl.when(is_first != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    kb = k_ref[0]
    vb = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]  # (bq, 1)
    dd = dd_ref[0]
    s = (
        lax.dot_general(q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        * scale
    )
    if has_bias:
        s = s + bias_ref[...]

    def _update(s):
        p_tile = jnp.exp(s - lse)  # exact probabilities via the saved LSE
        dp = lax.dot_general(do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p_tile * (dp - dd)).astype(kb.dtype)
        acc_ref[...] = acc_ref[...] + lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(needs_mask != 0)
    def _masked():
        rows = im_ref[p] * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = jm_ref[p] * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        _update(jnp.where(rows >= cols, s, _NEG_INF))

    @pl.when(needs_mask == 0)
    def _plain():
        _update(s)

    @pl.when(is_last != 0)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(jm_ref, im_ref, flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dd_ref, *refs, scale: float, bq: int, bk: int,
                has_bias: bool = False):
    """dk_j = Σ_i dSᵀ_ij · q_i · scale,  dv_j = Σ_i Pᵀ_ij · dO_i.

    Streams q/dO/LSE blocks over a kv-major flattened (j, i) pair grid with the
    dk/dv accumulators in VMEM scratch — no full-panel residency."""
    import jax.experimental.pallas as pl

    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = refs
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = refs

    p = pl.program_id(1)
    flags = flags_ref[p]
    is_first, is_last, needs_mask = flags & 1, flags & 2, flags & 4
    is_zero = flags & 8  # causal, Tk > Tq: no query attends this k-block

    @pl.when(is_first != 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    qb = q_ref[0]
    kb = k_ref[0]
    vb = v_ref[0]
    dob = do_ref[0]
    lse = lse_ref[0]  # (bq, 1)
    dd = dd_ref[0]
    s = (
        lax.dot_general(qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        * scale
    )
    if has_bias:
        s = s + bias_ref[...]

    def _update(s):
        p_tile = jnp.exp(s - lse)
        dp = lax.dot_general(dob, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p_tile * (dp - dd)).astype(qb.dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dv_acc_ref[...] = dv_acc_ref[...] + lax.dot_general(
            p_tile.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(needs_mask != 0)
    def _masked():
        rows = im_ref[p] * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = jm_ref[p] * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        _update(jnp.where(rows >= cols, s, _NEG_INF))

    @pl.when((needs_mask == 0) & (is_zero == 0))
    def _plain():
        _update(s)

    @pl.when(is_last != 0)
    def _finalize():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _pair_schedule_kv(nq: int, nk: int, bq: int, bk: int, causal: bool):
    """kv-major visit list for the dk/dv kernel: for each k-block j, the q-blocks
    i that attend to it (all of them when not causal; those at or beyond the
    diagonal otherwise). Same flag bits as :func:`_pair_schedule`, plus bit 8 =
    no query attends this k-block (causal with Tk > Tq): the step only writes
    zero gradients — without it those output blocks would hold uninitialized
    memory, since an unvisited grid block is never written."""
    jm, im, flags = [], [], []
    for j in range(nk):
        is_ = [
            i for i in range(nq)
            if not causal or i * bq + bq - 1 >= j * bk
        ]
        if not is_:
            jm.append(j)
            im.append(0)
            flags.append(1 | 2 | 8)
            continue
        for idx, i in enumerate(is_):
            f = (1 if idx == 0 else 0) | (2 if idx == len(is_) - 1 else 0)
            if causal and (j * bk + bk - 1 > i * bq):
                f |= 4
            jm.append(j)
            im.append(i)
            flags.append(f)
    return np.asarray(jm, np.int32), np.asarray(im, np.int32), np.asarray(flags, np.int32)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "bq", "bk", "interpret")
)
def _flash_bwd_pallas(q, k, v, o, do, lse, causal: bool, scale: float, bq: int,
                      bk: int, interpret: bool = False, bias=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with jax.enable_x64(False):
        *batch, tq, d = q.shape
        tk = k.shape[-2]
        bh = math.prod(batch) if batch else 1
        qr = q.reshape(bh, tq, d)
        kr = k.reshape(bh, tk, d)
        vr = v.reshape(bh, tk, d)
        dor = do.reshape(bh, tq, d)
        lser = lse.reshape(bh, tq, 1).astype(jnp.float32)
        # D_i = rowsum(dO ∘ O), one fused elementwise pass over the saved output
        dd = jnp.sum(
            dor.astype(jnp.float32) * o.reshape(bh, tq, d).astype(jnp.float32),
            axis=-1, keepdims=True,
        )

        has_bias = bias is not None
        bias_f32 = bias.astype(jnp.float32) if has_bias else None

        im, jm, flags = _pair_schedule(tq // bq, tk // bk, bq, bk, causal)
        dq_in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, p, im, jm, fl: (b, im[p], 0)),
            pl.BlockSpec((1, bk, d), lambda b, p, im, jm, fl: (b, jm[p], 0)),
            pl.BlockSpec((1, bk, d), lambda b, p, im, jm, fl: (b, jm[p], 0)),
            pl.BlockSpec((1, bq, d), lambda b, p, im, jm, fl: (b, im[p], 0)),
            pl.BlockSpec((1, bq, 1), lambda b, p, im, jm, fl: (b, im[p], 0)),
            pl.BlockSpec((1, bq, 1), lambda b, p, im, jm, fl: (b, im[p], 0)),
        ]
        dq_inputs = [qr, kr, vr, dor, lser, dd]
        if has_bias:
            dq_in_specs.append(
                pl.BlockSpec((bq, bk), lambda b, p, im, jm, fl: (im[p], jm[p]))
            )
            dq_inputs.append(bias_f32)
        dq_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(im)),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, bq, d), lambda b, p, im, jm, fl: (b, im[p], 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        )
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk, has_bias=has_bias),
            grid_spec=dq_spec,
            out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            interpret=interpret,
            compiler_params=None if interpret else _compiler_params(pltpu),
        )(jnp.asarray(im), jnp.asarray(jm), jnp.asarray(flags), *dq_inputs)

        jm2, im2, flags2 = _pair_schedule_kv(tq // bq, tk // bk, bq, bk, causal)
        dkv_in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, p, jm, im, fl: (b, im[p], 0)),
            pl.BlockSpec((1, bk, d), lambda b, p, jm, im, fl: (b, jm[p], 0)),
            pl.BlockSpec((1, bk, d), lambda b, p, jm, im, fl: (b, jm[p], 0)),
            pl.BlockSpec((1, bq, d), lambda b, p, jm, im, fl: (b, im[p], 0)),
            pl.BlockSpec((1, bq, 1), lambda b, p, jm, im, fl: (b, im[p], 0)),
            pl.BlockSpec((1, bq, 1), lambda b, p, jm, im, fl: (b, im[p], 0)),
        ]
        dkv_inputs = [qr, kr, vr, dor, lser, dd]
        if has_bias:
            dkv_in_specs.append(
                pl.BlockSpec((bq, bk), lambda b, p, jm, im, fl: (im[p], jm[p]))
            )
            dkv_inputs.append(bias_f32)
        dkv_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bh, len(jm2)),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, p, jm, im, fl: (b, jm[p], 0)),
                pl.BlockSpec((1, bk, d), lambda b, p, jm, im, fl: (b, jm[p], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        )
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, has_bias=has_bias),
            grid_spec=dkv_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
            ],
            interpret=interpret,
            compiler_params=None if interpret else _compiler_params(pltpu),
        )(jnp.asarray(jm2), jnp.asarray(im2), jnp.asarray(flags2), *dkv_inputs)
        return (
            dq.reshape(*batch, tq, d),
            dk.reshape(*batch, tk, d),
            dv.reshape(*batch, tk, d),
        )


def _fits(q, k, bq: int, bk: int, with_bias: bool = False) -> bool:
    """VMEM gate of the training entry: forward and backward all stream blocks through
    the grid, so residency is O(block) regardless of T — the gate only enforces even
    tiling, pair lists that fit SMEM and a sane per-step footprint (the forward's by
    :func:`_fwd_footprint`). One head width ``d`` for q, k and v: a narrower v goes
    through :func:`forward_blocks`."""
    tq, d = q.shape[-2], q.shape[-1]
    tk = k.shape[-2]
    if tq % bq or tk % bk:
        return False
    if tq % _BWD_BQ or tk % _BWD_BK:
        return False
    # the flattened pair schedules are O((T/b)²) int32 scalar-prefetch entries
    # living in SMEM — bound them (bwd uses the fixed _BWD blocks, check both)
    if (tq // bq) * (tk // bk) > _MAX_PAIRS:
        return False
    if (tq // _BWD_BQ) * (tk // _BWD_BK) > _MAX_PAIRS:
        return False
    itemsize = jnp.dtype(q.dtype).itemsize
    # backward per-step residency: s + p tiles (f32), accumulators, double-buffered
    # blocks, plus a double-buffered f32 bias block when a mask streams through
    bias_bwd = 8 * _BWD_BQ * _BWD_BK if with_bias else 0
    bwd = 8 * _BWD_BQ * _BWD_BK + 8 * _BWD_BK * d \
        + 2 * (_BWD_BQ + 2 * _BWD_BK) * d * itemsize * 2 + bias_bwd
    return max(_fwd_footprint(bq, bk, d, d, itemsize, with_bias), bwd) <= _vmem_budget()


def forward_blocks(q, k, v, with_mask: bool = False):
    """The largest preferred ``(bq, bk)`` with which the forward kernel alone runs
    ``q, k: (..., T, d)``, ``v: (..., Tk, dv)`` (k and v with q's heads or a divisor of
    them), or None: the sequence does not tile, the pair list outgrows SMEM, a type
    Mosaic does not take, or no block pair fits the VMEM budget by
    :func:`_fwd_footprint` (12 MiB of Mosaic's 16 MiB default scope). At d = 192,
    dv = 128 in bfloat16 and 32,768 tokens that is (1024, 1024), walked in (256, 512)
    sub-tiles: 528 steps a head, 6.0 MiB by the model. The same preference serves a
    window: at d = d_v = 128 under a band of 2,048 keys (1024, 1024) was the fastest of ten
    block pairs on the chip (11.2 ms a call; key blocks of 512 12.5, of 256 18.9: PERF.md,
    PR 31), because a row sweep's masked steps, not its skipped keys, set the time.
    ``with_mask``: the call streams packed mask words; every preferred key block divides
    their tile of 4,096 keys."""
    if any(t.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16) for t in (q, k, v)):
        return None
    return _preferred_blocks(q.shape[-2], k.shape[-2], q.shape[-1], v.shape[-1],
                             jnp.dtype(q.dtype).itemsize, with_mask)


def _preferred_blocks(tq: int, tk: int, d: int, dv: int, itemsize: int, with_mask: bool,
                      turned: int = 0):
    """The first of the preferences for ``itemsize`` that tiles ``(tq, tk)``, keeps the pair
    list in SMEM and fits the budget by :func:`_fwd_footprint`, or None."""
    for bq, bk in _FWD_BLOCK_PREFS.get(itemsize, ((512, 512),)):
        if tq % bq or tk % bk or (tq // bq) * (tk // bk) > _MAX_PAIRS:
            continue
        if _fwd_footprint(bq, bk, d, dv, itemsize, with_mask=with_mask,
                          turned=turned) <= _vmem_budget():
            return bq, bk
    return None


def flash_forward(q, k, v, causal: bool, scale: float, blocks, name=None,
                  interpret: bool = False, window=None, mask=None):
    """The forward kernel alone, for inference paths: v may be narrower or wider than
    q and k, k and v may have fewer heads than q (grouped heads, taken where they lie),
    ``window`` keeps row ``i`` to keys ``i - window < j <= i`` and skips the blocks
    outside that band, ``mask`` (packed words, ``sparse_index.pack_mask``) keeps row ``i``
    to the keys whose bits are set, all heads alike, ``blocks`` is what
    :func:`forward_blocks` chose, ``name`` names the Pallas call in device traces. No
    gradient is defined on this entry."""
    out, _ = _flash_pallas(q, k, v, causal, float(scale), *blocks, interpret=interpret,
                           name=name, window=window, mask=mask)
    return out


def latent_blocks(q, kv, k_rope, with_mask: bool = False, turned: bool = False):
    """The largest preferred ``(bq, bk)`` with which :func:`flash_latent` runs these operands
    (arrays or shapes), or None: q ``(..., H, T, dn + dr)``, ``kv`` ``(..., H, T, dn + dv)``,
    ``k_rope`` ``(..., T, dr)``, the widths ``dn`` and ``dv`` whole lane tiles with ``dv``
    dividing ``dn`` (so that k_nope and v are lane blocks of ``kv``), and what
    :func:`forward_blocks` asks of the concatenated operands, the turned rope lanes' table
    and copy counted (``turned``)."""
    dr = k_rope.shape[-1]
    dn, tk = q.shape[-1] - dr, kv.shape[-2]
    dv = kv.shape[-1] - dn
    if (q.ndim < 3 or kv.shape[:-1] != q.shape[:-1] or k_rope.shape != q.shape[:-3] + (tk, dr)
            or dn <= 0 or dv <= 0 or dn % _LANES or dv % _LANES or dn % dv
            or (turned and dr % 2)):
        return None
    if any(t.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16) or t.dtype != q.dtype
           for t in (kv, k_rope)):
        return None
    return _preferred_blocks(q.shape[-2], tk, dn + dr, dv, jnp.dtype(q.dtype).itemsize,
                             with_mask, dr if turned else 0)


def flash_latent(q, kv, k_rope, scale: float, blocks, turns=None, name=None,
                 interpret: bool = False, mask=None):
    """Causal latent attention with its operands as the projections leave them: q
    ``(..., H, T, dn + dr)``, ``kv`` the ``[k_nope | v]`` projection ``(..., H, T, dn + dv)``
    read in place as two lane blocks, and ``k_rope`` ``(..., T, dr)``, the one rope key of all
    ``H`` heads. Head ``h``'s key is ``[k_nope_h | k_rope]`` and its value ``v_h``; nothing is
    put together or repeated in HBM. ``turns``: ``(cos, sin)``, each ``(T, dr / 2)`` float32,
    the rotary positions of q's rope lanes as ``rotate_halves`` takes them (the halves
    layout), applied in the kernel; None where q's rope lanes are used as they are. ``mask``
    as in :func:`flash_forward`; ``blocks`` is what :func:`latent_blocks` chose. Returns
    ``(..., H, T, dv)``; no gradient is defined on this entry."""
    table = None
    if turns is not None:
        cos, sin = turns
        table = jnp.stack([jnp.concatenate([cos, cos], axis=-1),
                           jnp.concatenate([-sin, sin], axis=-1)])
    out, _ = _flash_pallas(q, kv, None, True, float(scale), *blocks, interpret=interpret,
                           name=name, mask=mask, k_rope=k_rope, turns=table)
    return out


def _as_bias(mask):
    """Normalize a (Tq, Tk) mask to an additive f32 bias: boolean True = attend
    (the dense-path convention in nn/attention.py), floats pass through."""
    if mask is None:
        return None
    if mask.dtype == jnp.bool_:
        return jnp.where(mask, jnp.float32(0), jnp.float32(_NEG_INF))
    return mask.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 6))
def flash_attention(q, k, v, causal: bool = False, scale=None, mask=None, window=None):
    """Exact attention with the flash (streaming-VMEM) forward on TPU.

    q: (..., Tq, D), k/v: (..., Tk, D); Tq/Tk must be multiples of the block
    sizes (callers fall back to the XLA path otherwise via :func:`use_flash`).
    ``mask`` is an optional exact-shape (Tq, Tk) boolean (True = attend) or
    additive float bias, shared across batch/heads and streamed blockwise like
    k/v. Float biases are NOT differentiated on this path (grad raises; use the
    XLA path for a learned bias). The backward is the flash backward (two Pallas
    kernels over the saved (O, LSE) residuals). All three kernels stream blocks
    through a flattened pair grid, so VMEM residency is O(block²) regardless of
    T — arbitrarily long sequences fit, and the (T, T) matrix never exists in
    HBM. The forward also takes a causal ``window`` and k / v with fewer heads than q
    (see :func:`flash_forward`); the backward computes neither and says so.
    """
    return _fwd(q, k, v, causal, scale, mask, window)[0]


def _fwd(q, k, v, causal, scale, mask, window):
    s = (1.0 / math.sqrt(q.shape[-1])) if scale is None else scale
    bias = _as_bias(mask)
    blocks = _fwd_blocks(q.dtype, q.shape[-2], k.shape[-2], with_bias=bias is not None)
    out, lse = _flash_pallas(q, k, v, causal, float(s), *blocks, bias=bias, window=window)
    return out, (q, k, v, out, lse, mask)


def _bwd(causal, scale, window, res, g):
    q, k, v, out, lse, mask = res
    if window is not None:
        # the backward kernels walk the causal schedule: they would return the gradient
        # of the unwindowed attention
        raise NotImplementedError(
            f"the flash backward kernels do not take a window (got window={window}): "
            "their schedule and mask are causal or dense only"
        )
    if k.shape[:-2] != q.shape[:-2]:
        raise NotImplementedError(
            "the flash backward kernels take as many key/value heads as query heads; "
            f"got {k.shape[:-2]} for {q.shape[:-2]} (grouped heads: the forward only)"
        )
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "the flash backward kernels take one head width for q, k and v; "
            f"got {q.shape[-1]} and {v.shape[-1]}"
        )
    if mask is not None and mask.dtype != jnp.bool_:
        # a float bias has a real gradient (Σ_{b,h} dS) that this backward does not
        # compute — fail loudly rather than silently training the bias to nothing.
        # use_flash only routes BOOL masks here; differentiable biases belong on
        # the XLA path, which differentiates scores + bias normally.
        raise NotImplementedError(
            "gradient through a float attention bias is not implemented on the "
            "flash path; boolean masks are gradient-free and fine — use the XLA "
            "attention path for a learned additive bias"
        )
    s = (1.0 / math.sqrt(q.shape[-1])) if scale is None else scale
    dq, dk, dv = _flash_bwd_pallas(
        q, k, v, out, g, lse, causal, float(s), _BWD_BQ, _BWD_BK, bias=_as_bias(mask)
    )
    # boolean masks have no tangent space; the zero cotangent is exact
    dmask = None if mask is None else jnp.zeros_like(mask, dtype=jnp.float32)
    return dq, dk, dv, dmask


flash_attention.defvjp(_fwd, _bwd)


def use_flash(q, k, v, mask, scale=None, interpret: bool = False) -> bool:
    """True when the Pallas forward applies: TPU backend, a static (or default)
    scale, a Mosaic-supported dtype, shapes that fit the VMEM budget/tiling, and
    a mask that is either absent or an exact-shape (Tq, Tk) BOOLEAN shared across
    batch/heads. Per-batch masks (e.g. (B, 1, 1, Tk) padding forms) and float
    biases take the XLA path — the former aren't streamable as one 2-D block,
    the latter have a bias gradient only the XLA path computes."""
    with_bias = mask is not None
    if with_bias and (
        mask.ndim != 2
        or mask.shape != (q.shape[-2], k.shape[-2])
        or mask.dtype != jnp.bool_
    ):
        return False
    if scale is not None and not isinstance(scale, (int, float)):
        # a traced scale can't become the kernel's static parameter; XLA path handles it
        return False
    # f64 inputs (legal framework-wide: x64 is enabled globally) must take the XLA
    # path — the kernel computes under enable_x64(False) and can't store to an f64 ref
    supported = (jnp.float32, jnp.bfloat16, jnp.float16)
    if any(t.dtype not in supported for t in (q, k, v)):
        return False
    if not interpret and jax.default_backend() != "tpu":
        return False
    return _fits(q, k, *_fwd_blocks(q.dtype, q.shape[-2], k.shape[-2], with_bias))
