"""Index scores of a learned sparse attention and the selection of each query's best keys,
as one Pallas call (TPU), and the packed mask that carries the selection to the flash kernel.

A *lightning indexer* scores every earlier token ``s`` for query ``t`` with a small attention
of its own, ``I[t, s] = sum_j w[t, j] relu(q[j, t] . k[s])`` over ``H`` index heads that share
one key a token, and the query then attends to the ``topk`` positions ``s <= t`` of largest
``I`` only. At 32,768 tokens the float32 score matrix is 4.3 GB and a ``lax.top_k`` of 2,048
among 32,768 is a whole sort a row; neither exists here.

**One grid step** takes 128 queries (they lie on the lanes) and holds the whole document's
index keys in VMEM (32,768 x 128 bfloat16 = 8 MB). *Scores*: for each block of 512 keys at
or under the diagonal, ``H`` products ``k_blk q_j^T`` (keys on the sublanes, so a head's
weight ``w[j]`` is a sublane broadcast and no lane is ever broadcast or reduced), ReLU, the
weighted sum over the heads in float32; the block is stored as sortable 32-bit integers
(:func:`_sortable`), positions past a query's own as the least integer. The score block
``(T, 128)`` never leaves VMEM (16 MB). *Selection without a sort*: the value of rank
``topk`` a query is built one binary digit at a time, 32 counting passes over the live part
of the block (``count(key >= candidate) >= topk`` keeps the digit), which is exact; ties at
that value go to the lower positions, found by a second bisection on the position (15
passes) so that exactly ``topk`` keys stay, as ``lax.top_k`` would leave them. A query with
fewer than ``topk`` earlier tokens keeps them all.

**The packed mask.** The selection leaves as 32-bit words, ``(T, mask_words(T))``: word lane
``l`` of word tile ``B`` holds, in its bit ``b``, key ``4096 B + 128 b + l``. A ``(rows, 128)``
tile of words is thus the mask of 4,096 keys, a lane tile of 128 keys is one shift and one
``and`` away, and a document of 32,768 tokens is 134 MB where a byte mask is 1.07 GB and
``int32[T, topk]`` indices 268 MB. :func:`pack_mask` and :func:`unpack_mask` are the plain
forms; the flash forward (``flash_attention._kernel`` with ``has_mask``) reads the words.

:func:`dsa_index_plain` is the ``jnp`` form (dense scores, ``lax.top_k``, a scattered mask):
the CPU path and the fallback that ``fallback.nn.dsa`` counts, for sizes at which a (T, T)
tensor may live.

No reference counterpart.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import diagnostics

__all__ = ["dsa_index", "dsa_index_plain", "decline_reason", "available", "pack_mask",
           "unpack_mask", "mask_words", "index_scores", "select_plain"]

_LANES = 128
# keys behind one lane tile of mask words: 32 bits of 128 lanes
WORD_KEYS = 32 * _LANES
_INT_MIN = -2**31
# keys of one product and of one counting step, and heads of one straight-line region of the
# scoring loop: Mosaic overlaps one head's product with another's ReLU and weighted add only
# inside a region, and a rolled loop of single heads left the MXU idle three cycles in four.
# A call at 32,768 tokens, 64 heads of 128 (my chip runs, PR 37, chiprun_out/b37): (256, 1)
# 211.4 ms, (256, 4) 107.4, (256, 16) 80.2, (512, 8) 79.1, (512, 16) 74.7, (512, 32) 72.3,
# (256, 64) 72.2, (1024, 8) 93.9; the products alone are 44.7 ms at the MXU's peak
_KEY_BLOCK = 512
_HEAD_UNROLL = 16
# what a call may ask of the v5e's 128 MiB of VMEM, and what it asks for above its own count
_VMEM_CAP = 100 * 2**20
_VMEM_MARGIN = 8 * 2**20


def mask_words(t: int) -> int:
    """Words a row of the packed mask over ``t`` keys holds: whole lane tiles."""
    return -(-t // WORD_KEYS) * _LANES


def pack_mask(mask):
    """A boolean ``mask`` (Tq, Tk) as words ``(Tq, mask_words(Tk))`` int32: bit ``b`` of
    lane ``l`` of word tile ``B`` is key ``4096 B + 128 b + l``; keys past ``Tk`` read 0."""
    tq, tk = mask.shape
    tiles = mask_words(tk) // _LANES
    m = jnp.pad(mask, ((0, 0), (0, tiles * WORD_KEYS - tk))).reshape(tq, tiles, 32, _LANES)
    bit = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    words = jnp.sum(m.astype(jnp.uint32) << bit, axis=2, dtype=jnp.uint32)  # bits are distinct
    return lax.bitcast_convert_type(words, jnp.int32).reshape(tq, tiles * _LANES)


def unpack_mask(words, tk: int):
    """The boolean mask (Tq, ``tk``) that :func:`pack_mask` packed."""
    tq = words.shape[0]
    w = lax.bitcast_convert_type(words, jnp.uint32).reshape(tq, -1, 1, _LANES)
    bit = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    return (((w >> bit) & jnp.uint32(1)) != 0).reshape(tq, -1)[:, :tk]


def index_scores(q, k, w):
    """``I[t, s] = sum_j w[j, t] relu(q[j, t] . k[s])`` in float32: ``q`` (H, T, D), ``k``
    (T, D), ``w`` (H, T) float32. The plain form: it holds (H, T, T)."""
    exact = q.dtype == jnp.float32
    s = jnp.einsum("htd,sd->hts", q, k, preferred_element_type=jnp.float32,
                   precision=lax.Precision.HIGHEST if exact else None)
    return jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None].astype(jnp.float32), axis=0)


def select_plain(scores, topk: int):
    """The boolean mask (T, T) of each row's ``min(topk, t + 1)`` largest ``scores[t, s]``
    among ``s <= t``; ties go to the lower position (``lax.top_k``'s order)."""
    t = scores.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    causal = pos[:, None] >= pos[None, :]
    _, kept = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    mask = jnp.zeros((t, t), jnp.bool_).at[pos[:, None], kept].set(True)
    return mask & causal  # a row with fewer than topk earlier tokens drew from past its own


def dsa_index_plain(q, k, w, topk: int):
    """The packed selection in ``jax.numpy``: dense scores, ``lax.top_k``, a scattered mask."""
    return pack_mask(select_plain(index_scores(q, k, w), topk))


def _sortable(x):
    """Float32 ``x`` as int32 whose signed order is ``x``'s (-0 under +0, NaN at the ends)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _kernel(q_ref, k_ref, w_ref, o_ref, key_ref, *, topk: int, bk: int, pos_bits: int,
            unroll: int):
    import jax.experimental.pallas as pl

    heads, bq = w_ref.shape
    tiles = o_ref.shape[1] // _LANES
    row0 = pl.program_id(0) * bq
    blocks = (row0 + bq - 1) // bk + 1  # key blocks at or under the diagonal
    query = row0 + lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    # 16-bit operands are one MXU pass whatever the process-wide default says (Mosaic
    # refuses them at "highest"); float32 operands multiply exactly
    precision = lax.Precision.DEFAULT if q_ref.dtype.itemsize < 4 else lax.Precision.HIGHEST

    def position(r0, rows):
        return r0 + lax.broadcasted_iota(jnp.int32, (rows, bq), 0)

    def score(b, carry):
        r0 = pl.multiple_of(b * bk, bk)
        keys = k_ref[pl.ds(r0, bk), :]

        def some_heads(g, acc):  # one straight-line region: the products do not wait for the adds
            for j in range(unroll):
                j = g * unroll + j
                s = lax.dot_general(keys, q_ref[j], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32, precision=precision)
                acc = acc + jnp.maximum(s, 0.0) * w_ref[pl.ds(j, 1), :]
            return acc

        total = lax.fori_loop(0, heads // unroll, some_heads, jnp.zeros((bk, bq), jnp.float32))
        key_ref[pl.ds(r0, bk), :] = jnp.where(position(r0, bk) <= query, _sortable(total),
                                              jnp.int32(_INT_MIN))
        return carry

    lax.fori_loop(0, blocks, score, 0)

    def blank(b, carry):  # the rest of the last word tile in use: what an earlier step left
        key_ref[pl.ds(pl.multiple_of(b * bk, bk), bk), :] = jnp.full((bk, bq), _INT_MIN, jnp.int32)
        return carry

    per_tile = WORD_KEYS // bk
    lax.fori_loop(blocks, (blocks + per_tile - 1) // per_tile * per_tile, blank, 0)

    def count(hit):
        """Keys a query (a lane) has under ``hit(keys, first position)`` among the live rows."""
        def part(b, acc):
            r0 = pl.multiple_of(b * bk, bk)
            ones = hit(key_ref[pl.ds(r0, bk), :], r0).astype(jnp.int32)
            return acc + jnp.sum(ones.reshape(bk // 8, 8, bq), axis=0)

        acc = lax.fori_loop(0, blocks, part, jnp.zeros((8, bq), jnp.int32))
        return jnp.sum(acc, axis=0, keepdims=True)

    def digit(b, found):  # the rank's value in unsigned order, from its leading digit down
        trial = found | lax.shift_left(jnp.int32(1), 31 - b)
        enough = count(lambda keys, r0: keys >= (trial ^ jnp.int32(_INT_MIN))) >= topk
        return jnp.where(enough, trial, found)

    found = lax.fori_loop(0, 32, digit, jnp.zeros((1, bq), jnp.int32))
    # fewer than topk earlier tokens: found is 0, the masked positions' own value; the least
    # value a score can have keeps every earlier token and no masked one
    rank = jnp.maximum(found ^ jnp.int32(_INT_MIN), jnp.int32(_INT_MIN + 1))
    ties = topk - count(lambda keys, r0: keys > rank)  # keys equal to rank that stay

    def place(b, last):  # the largest position under which fewer than ``ties`` equal keys lie
        trial = last | lax.shift_left(jnp.int32(1), pos_bits - 1 - b)
        fewer = count(lambda keys, r0: (keys == rank) & (position(r0, bk) < trial)) < ties
        return jnp.where(fewer, trial, last)

    last = lax.fori_loop(0, pos_bits, place, jnp.zeros((1, bq), jnp.int32))

    for tile in range(tiles):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)

        @pl.when(tile * WORD_KEYS < blocks * bk)
        def _pack():
            def bit(b, words):
                r0 = pl.multiple_of(tile * WORD_KEYS + b * _LANES, _LANES)
                keys = key_ref[pl.ds(r0, _LANES), :]
                stays = (keys > rank) | ((keys == rank) & (position(r0, _LANES) <= last))
                return words | lax.shift_left(stays.astype(jnp.int32), b)

            words = lax.fori_loop(0, 32, bit, jnp.zeros((_LANES, bq), jnp.int32))
            o_ref[:, lanes] = words.T  # keys' lanes on the lanes, queries on the sublanes

        @pl.when(tile * WORD_KEYS >= blocks * bk)
        def _above():
            o_ref[:, lanes] = jnp.zeros((bq, _LANES), jnp.int32)


def _key_block(t: int) -> int:
    return _KEY_BLOCK if t % _KEY_BLOCK == 0 else _LANES


def _footprint(heads: int, t: int, d: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step holds: the document's keys and the step's queries,
    weights and words double-buffered, the score block, and the live tiles of a product."""
    bk = _key_block(t)
    t_pad = mask_words(t) * 32
    blocks = 2 * (t * d * itemsize + heads * _LANES * (d * itemsize + 4) + t_pad // 32 * _LANES * 4)
    return blocks + t_pad * _LANES * 4 + 6 * bk * _LANES * 4


def available(interpret: bool = False) -> bool:
    """Whether the kernel can run here: on a TPU backend, or interpreted anywhere."""
    return interpret or jax.default_backend() == "tpu"


def decline_reason(q, k, w) -> Optional[str]:
    """Why the kernel is not compiled for ``q`` (H, T, D), ``k`` (T, D), ``w`` (H, T), or
    ``None`` where it is; it says no before Mosaic does."""
    kinds = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
    if q.dtype not in kinds or k.dtype != q.dtype:
        return f"operands {q.dtype} x {k.dtype}: the kernel takes bfloat16 or float32"
    heads, t, d = q.shape
    if t % _LANES or d % _LANES or heads % 8:
        return (f"tiles: T={t} and D={d} must be whole lane tiles of {_LANES} and the "
                f"{heads} heads whole sublane tiles of 8")
    need = _footprint(heads, t, d, q.dtype.itemsize)
    if need + _VMEM_MARGIN > _VMEM_CAP:
        return (f"VMEM: the keys and the score block of T={t}, D={d} hold {need >> 20} MiB "
                f"of {_VMEM_CAP >> 20}")
    return None


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def dsa_index(q, k, w, topk: int, interpret: bool = False):
    """The packed selection ``(T, mask_words(T))`` int32 of index queries ``q`` (H, T, D),
    index keys ``k`` (T, D) and head weights ``w`` (H, T) float32: bit ``s`` of row ``t``
    (:func:`pack_mask`'s layout) says that ``s <= t`` is among the ``min(topk, t + 1)``
    largest ``I[t, .]``. Callers ask :func:`decline_reason` first. Named ``dsa_index_fwd``
    in a device trace. No gradient is defined on this entry."""
    import jax.experimental.pallas as pl  # deferred so CPU-only processes never pay it
    from jax.experimental.pallas import tpu as pltpu

    # the framework enables x64 globally; Mosaic only legalizes i32 scalars
    with jax.enable_x64(False):
        heads, t, d = q.shape
        if diagnostics._enabled:  # trace time only: a trace of the path that took the kernel
            diagnostics.counter("kernels.dsa.index")
        words = mask_words(t)
        need = _footprint(heads, t, d, q.dtype.itemsize)
        return pl.pallas_call(
            functools.partial(_kernel, topk=topk, bk=_key_block(t),
                              pos_bits=max(1, (t - 1).bit_length()),
                              unroll=_HEAD_UNROLL if heads % _HEAD_UNROLL == 0 else 1),
            grid=(t // _LANES,),
            in_specs=[
                pl.BlockSpec((heads, _LANES, d), lambda i: (0, i, 0)),
                pl.BlockSpec((t, d), lambda i: (0, 0)),  # fetched once: its index never moves
                pl.BlockSpec((heads, _LANES), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((_LANES, words), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((t, words), jnp.int32),
            scratch_shapes=[pltpu.VMEM((words * 32, _LANES), jnp.int32)],
            interpret=interpret,
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),  # the score block is one scratch
                vmem_limit_bytes=need + _VMEM_MARGIN),  # under _VMEM_CAP by the gate
            name="dsa_index_fwd",
        )(q, k, w.astype(jnp.float32))
