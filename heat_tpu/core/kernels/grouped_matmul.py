"""Grouped gated-SiLU products over an expert-sorted, block-padded buffer, and the weighted
sum that reads the buffer back by token: two Pallas calls (TPU).

``nn/moe.py`` sorts its (token, expert) pairs by expert and pads every expert's group to
whole blocks of ``block_rows`` rows, so a block of the buffer belongs to exactly one
expert and no group edge lies inside a block. The kernel's grid is the buffer's blocks.
Scalar-prefetched, it is told which expert each block belongs to and how many blocks are
used; the weights' ``BlockSpec`` index maps read the expert from that map, so an expert's
three matrices are fetched when the expert changes (the pipeline fetches the next
expert's under the current expert's last block) and stay in VMEM over its blocks. A
block past the used count does no work, and its index maps repeat the last used block's,
so it fetches and writes nothing: its rows of the output are **never written**. The
combine, the one reader of the output, selects on what was written; it never multiplies an
unwritten row by 0.

**The rows come through the sorted index.** The buffer itself is never made: the kernel
takes the tokens ``x`` (T, d) where they lie in HBM and ``source`` (rows,), the token behind
each buffer row, and a step starts the row DMAs of the NEXT block (one token a DMA, double
buffered) in the same straight-line region as the current block's products, so the
scalar core issues them under the MXU's work; one wait on the block's semaphore (the rows'
bytes add up to the buffer's) opens the next step. For that a token has to be a whole
number of (8, 128) tiles of 32-bit words: :func:`_words` packs bfloat16 columns ``c`` and
``c + d / 2`` into one word, pads a row to whole tiles and lays the rows out one after
another; the step reads lane tile ``s`` of its rows with a sublane stride and unpacks with
a shift and a mask. The last used step fetches its own block again (no branch in the
body) and drains it before the kernel ends. **The output leaves in the same words**
(:func:`_pack` in the step's epilogue, stored with the same sublane stride), so that its
reader can fetch a row as one DMA too; ``(rows, d)`` in the streams' type is never made. A
row of 2048 bfloat16 is 4 KB either way, one of 2560 or 3584 is padded to 8 KB.

**One step** is ``W_down (silu(W_gate x) * W_up x)`` on one block of ``block_rows`` rows, in
the numerical form of :func:`heat_tpu.nn.modules.gated_silu`: operands as they are
stored, float32 accumulation of gate and up, ``silu(gate) * up`` in float32, rounded to
the activation type, float32 accumulation of the down product, rounded once. 16-bit
operands state ``Precision.DEFAULT`` (one MXU pass, whatever the process-wide default
says: Mosaic refuses them at "highest"), float32 operands ``Precision.HIGHEST``. The
hidden activation ``(block_rows, h)`` (of a slab, where the expert is walked in slabs) stays in
VMEM and never goes through HBM.

**What bounds a step** (TPU v5e, bfloat16; my chip runs, PR 32): the MXU, then the DMA
issue. On a buffer gathered beforehand, at ``d`` 2048, ``h`` 1024 a 512-row step as one
straight-line region is 45,658 bundles holding 12,288 ``vmatmul`` of 16 rows on four MXUs,
49,152 MXU cycles, and runs at 193.8 TFLOP/s on the rows it multiplies, padding included:
98% of the peak, whatever chunk the hidden dimension is walked in (256, 512 or whole:
19.11-19.12 ms a layer of 575 used blocks). At ``d`` 3584 the same region is 70,525 bundles,
4.5 MB of code, and fell to 165 TFLOP/s (84%): the core stalls on its instruction fetch;
walked as a rolled loop of 256-row chunks it is at 193.8 again (16.76 ms a layer for 19.62).
Chunks of 128 rows cost about 1% more at either width (19.39 for 19.14, 16.85 for 16.76).
Reading the rows through ``source`` adds 6.3 ms a layer at ``d`` 2048 and 4.8 at 3584 (512
DMA starts a step are ~11 us of issue, not all of it hidden) where XLA's gather of the buffer
took 11.5 and 9.0. Each DMA start is its own operation in the kernel's body, which every
process traces and lowers anew before the compile cache is asked: with a body of 512 rows
that was 10 s of set-up on the benchmark's host, with :func:`_row_chunk`'s 128 (started from
a loop that is unrolled only when lowered) it is inside the noise, at the same time a layer.

**The combine** (``moe_combine_fwd``, :func:`combine`) is ``y[t] = sum_j w[t, j] row(slot[t,
j])`` over the ranks ``j`` whose pair is held here. Its grid is blocks of tokens whose pairs
fill one SMEM tile (1,024: 128 tokens at top-8, 256 at top-4), laid out block by block with
the ranks leading. A step starts the row DMAs of the NEXT block into the other half of a
double buffer and sums the current block: per rank a strided read of the block's rows, a
select on ``held``, the unpacking, the product with the rank's weight and the add, all
float32, **in the order of the ranks**; the gathered rows never leave VMEM. Two forms, chosen
by what is known when traced. *Every expert held here*: the tile is the pairs' rows, and each
straight-line body of the sum starts :data:`_CHUNK_ROWS` DMAs of the next block first, so the
scalar core issues them under the vector work; one wait a block. *A share held*: a pair held
elsewhere (``slot >= rows``) starts no DMA and is waited for by nobody. The caller sorts each
block's tile so that it lists the held pairs alone, as ``row * 1024 + pair``, and counts them;
the step walks that list in a rolled loop, and its wait takes the count one binary digit at a
time (the rows' bytes add up, whichever rows they were). A branch a slot over all 1,024 cost
as much as a DMA start each (5.1 ms a layer of 262,144 pairs with 21.5% held, against 3.2;
my chip runs, PR 36). XLA's form gathers a row a pair to HBM (34 ns a row: it is bound by
descriptors, not bytes) and reads ``(k, T, d)`` back for the sum: 15.0, 10.9 and 9.4 ms a layer
at the three cells' shapes where the kernel takes 3.2, 4.1 and 2.9. The sum walks a row's lane
tiles in a rolled loop: unrolled in Python it cost every process 0.4 s more of tracing for
0.05 to 0.23 ms a layer.

**VMEM.** Whole experts are resident where they fit, in both pipeline buffers: at ``d`` 2048,
``h`` 1024 in bfloat16 (Trinity-Mini) 2 x 12.6 MB of weights, at ``d`` 3584 (Xing4.0) 2 x 22 MB.
:func:`_footprint` counts a step's bytes from the shapes, the call raises Mosaic's
``vmem_limit_bytes`` to that count plus a margin, and :func:`decline_reason` declines what
would pass :data:`_VMEM_CAP` of the v5e's 128 MiB before Mosaic does. **Where the whole expert
does not fit** (DeepSeek-V3.2: ``d`` 7168, ``h`` 2048, 179 MiB) a step walks the hidden width
in slabs (PR 38): the grid is (blocks, slabs), gate and up come as ``(1, d, hs)``, down as
``(1, hs, d)``, and a float32 ``(block_rows, d)`` scratch sums the slabs' down products;
:func:`_pack` runs on the last slab only, so the one change to the numbers is that float32 sum
taken slab by slab. Each slab starts its share of the next block's rows. :func:`_slab` picks
``hs`` from the shapes: all of ``h`` wherever the expert fits, so the other cells trace the
body they traced before, else ``h`` halved until the step fits (512 at ``d`` 7168 and 256-row
blocks: 2 x 22 MB of weights, 71 MiB in all). An expert's weights are then read again for
every block: blocks past the used count repeat the last used block's last slab and fetch
nothing. The combine holds two blocks of 1,024 gathered rows (2 x 4, 2 x 8 or, at ``d`` 7168,
2 x 16 MB) and its float32 output block (:func:`_combine_footprint`). **One gate** serves both
calls: the buffer's layout ties them.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import diagnostics

__all__ = ["grouped_gated_silu", "combine", "available", "decline_reason", "block_map"]

_LANES = 128
# entries of ``source`` a step sees in SMEM: XLA keeps s32[n] in tiles of 1,024
_SOURCE_TILE = 1024
# multiply-adds of the largest straight-line body measured at the MXU's pace: three products
# of (512, 2048) x 1024 in bfloat16, 2.97 MB of code (PERF.md, PR 32)
_BODY_MACS = 3 * 512 * 2048 * 1024
# rows of a step's body: the MXU keeps its pace down to 128 rows a chunk (19.39 ms a layer
# for the whole block's 19.14 at d 2048, 16.85 for 16.76 at d 3584), and every row of the
# body is a DMA start that each process traces and lowers anew, inside set-up
_CHUNK_ROWS = 128
# what a call may ask of the v5e's 128 MiB of VMEM, and what it asks for above its own count
_VMEM_CAP = 100 * 2**20
_VMEM_MARGIN = 8 * 2**20


def _row_chunk(block_rows: int, d: int, h: int, itemsize: int) -> int:
    """Rows of a step's straight-line body: :data:`_CHUNK_ROWS` where they divide
    ``block_rows`` (else the block whole), halved (whole sublane tiles) while the three
    products pass :data:`_BODY_MACS`; float32 operands at ``HIGHEST`` are six MXU passes an
    operand pair."""
    passes, sublanes = (1, 16) if itemsize < 4 else (6, 8)
    br = _CHUNK_ROWS if block_rows % _CHUNK_ROWS == 0 else block_rows
    while 3 * br * d * h * passes > _BODY_MACS and br % (2 * sublanes) == 0:
        br //= 2
    return br


def _token_tiles(d: int, itemsize: int) -> Tuple[int, int]:
    """``(tiles, padded)``: lane tiles of 32-bit words a token of ``d`` elements fills, and the
    whole (8, 128) tiles it is padded to so that one DMA moves it."""
    tiles = d * itemsize // 4 // _LANES
    return tiles, -(-tiles // 8) * 8


def _footprint(d: int, h: int, block_rows: int, x_size: int, w_size: int,
               hs: Optional[int] = None) -> int:
    """Bytes of VMEM one grid step holds: a slab of ``hs`` of the hidden width (all ``h`` by
    default) of one expert's three weight matrices and the output block of padded 32-bit rows,
    double-buffered; the two gathered blocks of the same size; where ``hs`` is a part of ``h``,
    the float32 sum of the down product over the block; and the live tiles of a row chunk: its
    tokens unpacked, gate and up in float32, the hidden activation, the down product."""
    hs = h if hs is None else hs
    br = _row_chunk(block_rows, d, hs, w_size)
    weights = 2 * 3 * d * hs * w_size
    blocks = 2 * 2 * block_rows * _token_tiles(d, x_size)[1] * _LANES * 4
    total = 0 if hs == h else block_rows * d * 4
    tiles = br * (x_size * d + 2 * 4 * hs + x_size * hs + 4 * d)
    return weights + blocks + total + tiles


def _slab(d: int, h: int, block_rows: int, x_size: int, w_size: int) -> int:
    """The part of the hidden width a grid step multiplies: all of ``h`` wherever the whole
    expert fits under :data:`_VMEM_CAP`, else ``h`` halved until its slab does, in whole lane
    tiles; 0 where none does, or where the slabs outnumber the rows of a chunk (each slab
    starts an equal share of them)."""
    hs = h
    while _footprint(d, h, block_rows, x_size, w_size, hs) + _VMEM_MARGIN > _VMEM_CAP:
        if hs % (2 * _LANES):
            return 0
        hs //= 2
    return 0 if _row_chunk(block_rows, d, hs, w_size) % (h // hs) else hs


def available(interpret: bool = False) -> bool:
    """Whether the kernel can run here: on a TPU backend, or interpreted anywhere."""
    return interpret or jax.default_backend() == "tpu"


def decline_reason(x, rows: int, w_gate, w_down, block_rows: int, top_k: int) -> Optional[str]:
    """Why the two kernels are not compiled for tokens ``x`` (T, d) of ``top_k`` pairs each
    behind a sorted buffer of ``rows`` rows against stacked ``w_gate`` (count, d, h) and
    ``w_down`` (count, h, d), or ``None`` where they are: one gate for the products and the
    combine, whose buffer only they read and write, and it says no before Mosaic does."""
    kinds = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
    if x.dtype not in kinds or w_gate.dtype not in kinds or w_down.dtype != w_gate.dtype:
        return f"streams {x.dtype} x {w_gate.dtype}: the kernel takes bfloat16 or float32"
    d, h = x.shape[1], w_gate.shape[-1]
    sublanes, lanes = 32 // x.dtype.itemsize, _LANES * 4 // x.dtype.itemsize
    if d % lanes or h % _LANES:
        return (f"tiles: d={d} must be whole lane tiles of {lanes} {x.dtype} (32-bit words) "
                f"and h={h} of {_LANES}")
    if (block_rows % sublanes or rows % block_rows
            or (_SOURCE_TILE % block_rows and block_rows % _SOURCE_TILE)):
        return (f"tiles: block_rows={block_rows} must be whole sublane tiles of {sublanes}, "
                f"divide rows={rows}, and divide or be divided by {_SOURCE_TILE}")
    if not _slab(d, h, block_rows, x.dtype.itemsize, w_gate.dtype.itemsize):
        need = _footprint(d, h, block_rows, x.dtype.itemsize, w_gate.dtype.itemsize)
        return (f"VMEM: an expert of d={d}, h={h} in {w_gate.dtype} with blocks of {block_rows} "
                f"rows holds {need >> 20} MiB of {_VMEM_CAP >> 20}, and no slab of its hidden "
                f"width fits")
    need = _combine_footprint(d, top_k, x.dtype.itemsize)
    if top_k * 8 > _SOURCE_TILE or need + _VMEM_MARGIN > _VMEM_CAP:
        return (f"combine: top_k={top_k} pairs a token of d={d}: a step takes 8 tokens or more, "
                f"{_SOURCE_TILE} pairs at most, and holds {need >> 20} MiB of {_VMEM_CAP >> 20}")
    if rows * _SOURCE_TILE >= 2**31:
        return f"combine: rows={rows} times {_SOURCE_TILE} pairs a step pass 32 bits"
    return None


def block_map(blocks, n_blocks: int) -> Tuple[jax.Array, jax.Array]:
    """``(block_expert (n_blocks,) int32, used (1,) int32)`` from each held expert's
    number of blocks: the expert whose rows block ``j`` of the sorted buffer holds, and
    the number of blocks in use. Past the used count the map repeats the last used
    block's expert, so a step there fetches no weights."""
    ends = jnp.cumsum(blocks.astype(jnp.int32), dtype=jnp.int32)
    used = ends[-1:]
    j = jnp.minimum(jnp.arange(n_blocks, dtype=jnp.int32), jnp.maximum(used - 1, 0))
    expert = jnp.searchsorted(ends, j, side="right", method="compare_all").astype(jnp.int32)
    return jnp.minimum(expert, blocks.shape[0] - 1), used


def _words(x):
    """Tokens ``x`` (T, d) as 32-bit words ``(T * padded, 128)``: token ``t`` is rows
    ``t * padded ..`` (its lane tiles one after another, padded to whole (8, 128) tiles), so
    a token is one aligned, contiguous DMA. A bfloat16 word holds columns ``c`` (low half) and
    ``c + d / 2`` (high half); a float32 word is the element."""
    t, d = x.shape
    if x.dtype.itemsize == 2:
        low, high = (lax.bitcast_convert_type(part, jnp.uint16).astype(jnp.uint32)
                     for part in (x[:, :d // 2], x[:, d // 2:]))
        words = low | (high << 16)
    else:
        words = lax.bitcast_convert_type(x, jnp.uint32)
    tiles, padded = _token_tiles(d, x.dtype.itemsize)
    words = jnp.pad(words, ((0, 0), (0, (padded - tiles) * _LANES)))
    return words.reshape(t * padded, _LANES)


def _pack(y, dtype):
    """Float32 ``y`` (n, d), rounded once to ``dtype``, as the 32-bit words of :func:`_words`
    ``(n, tiles * 128)``, inside a kernel: a bfloat16's bits are the top half of the float32
    that holds its value."""
    if jnp.dtype(dtype).itemsize == 4:
        return lax.bitcast_convert_type(y, jnp.uint32)
    bits = lax.bitcast_convert_type(y.astype(dtype).astype(jnp.float32), jnp.uint32)
    half = y.shape[1] // 2
    return (bits[:, :half] >> 16) | bits[:, half:]


def _halves(words):
    """The two float32 values a word of bfloat16 columns ``c`` and ``c + d / 2`` holds."""
    return (lax.bitcast_convert_type(words << 16, jnp.float32),
            lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), jnp.float32))


def _kernel(expert_ref, used_ref, now_ref, next_ref, x_hbm, wg_ref, wu_ref, wd_ref, o_ref,
            buf, sem, *total, br: int, dtype, slabs: int = 1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, used = pl.program_id(0), used_ref[0]
    d = wd_ref.shape[-1]
    tiles, padded = _token_tiles(d, jnp.dtype(dtype).itemsize)
    rows = o_ref.shape[0] // padded
    per = now_ref.shape[0] // rows  # blocks whose sources one SMEM tile holds
    slot = i % 2
    # 16-bit operands are one MXU pass whatever the process-wide default says (Mosaic
    # refuses them at "highest"); float32 operands multiply exactly, as `contract` does
    precision = lax.Precision.DEFAULT if wg_ref.dtype.itemsize < 4 else lax.Precision.HIGHEST
    # the hidden width in slabs: grid axis 1 walks them, ``total`` sums the down product
    j, last = (pl.program_id(1), slabs - 1) if slabs > 1 else (0, 0)

    def fetch(src_ref, block, row, to):
        """Start the DMA of buffer row ``row`` of ``block`` into gathered block ``to``."""
        token = src_ref[(block % per) * rows + row]
        pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(token * padded, 8), padded), :],
            buf.at[to, pl.ds(pl.multiple_of(row * padded, 8), padded), :], sem.at[to]).start()

    def wait(which):  # one wait for a whole block: its rows' bytes add up to the buffer's
        pltpu.make_async_copy(x_hbm.at[pl.ds(0, rows * padded), :], buf.at[which],
                              sem.at[which]).wait()

    def tokens(r0):
        """Rows ``r0 .. r0 + br`` of the gathered block, unpacked to ``(br, d)``: lane tile
        ``s`` of a row is every ``padded``-th sublane row of the buffer."""
        words = [buf[slot, pl.ds(r0 * padded + s, br, stride=padded), :] for s in range(tiles)]
        if jnp.dtype(dtype).itemsize == 4:
            return jnp.concatenate([lax.bitcast_convert_type(w, dtype) for w in words], axis=1)
        low, high = zip(*(_halves(w) for w in words))
        return jnp.concatenate([half.astype(dtype) for half in low + high], axis=1)

    def dot(a, b):
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32, precision=precision)

    def out(r0, y):  # out as it came in: lane tile ``s`` of a row is the row's ``s``-th sublane row
        words = _pack(y, dtype)
        for s in range(tiles):
            o_ref[pl.ds(r0 * padded + s, br, stride=padded), :] = words[:, s * _LANES:(s + 1) * _LANES]

    def chunk(r0):
        # the next block's rows first, in program order: their DMA starts are scalar work
        # that the scheduler places under this chunk's products; walked in slabs, each slab
        # starts its share of the chunk's rows (faster than all of them in the first slab:
        # PERF.md, PR 38)
        following = jnp.minimum(i + 1, used - 1)
        share = br // slabs
        at = r0 if slabs == 1 else r0 + j * share

        def one(row, carry):
            fetch(next_ref, following, at + row, 1 - slot)
            return carry

        lax.fori_loop(0, share, one, 0, unroll=True)  # traced once, unrolled when lowered
        x = tokens(r0)
        gate, up = dot(x, wg_ref[0]), dot(x, wu_ref[0])
        hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        y = dot(hidden, wd_ref[0])
        if slabs == 1:
            return out(r0, y)
        part = total[0].at[pl.ds(r0, br), :]

        @pl.when(j == 0)
        def _open():
            part[...] = y

        if slabs > 2:
            @pl.when((j > 0) & (j < last))
            def _add():
                part[...] += y

        @pl.when(j == last)
        def _close():  # the one rounding, on the last slab only
            out(r0, part[...] + y)

    def on_slab(when, at):  # on one slab of the step only, where there are slabs
        return when if slabs == 1 else when & (j == at)

    @pl.when(on_slab((i == 0) & (used > 0), 0))
    def _first():  # nothing is in flight yet: the first block's rows, once a call
        def one(row, carry):
            fetch(now_ref, 0, row, 0)
            return carry

        lax.fori_loop(0, rows, one, 0)

    @pl.when(i < used)
    def _block():
        if slabs == 1:
            wait(slot)
        else:  # the block's rows landed before its first slab
            pl.when(j == 0)(functools.partial(wait, slot))
        if br == rows:
            chunk(0)
        else:  # rolled: the body is compiled once (its size is what the chunk was cut for)
            def body(r, carry):
                chunk(pl.multiple_of(r * br, br))
                return carry

            lax.fori_loop(0, rows // br, body, 0)

    @pl.when(on_slab(i + 1 == used, last))
    def _drain():  # the last used step fetched its own block again: nothing stays in flight
        wait(1 - slot)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret", "sub"))
def _grouped_pallas(x, source, w_gate, w_up, w_down, block_expert, used, block_rows: int,
                    interpret: bool = False, sub: Optional[int] = None):
    """``sub`` is the row chunk of a step's body, by default what :func:`_row_chunk` reads
    off the shapes (a hook for tests and sweeps)."""
    import jax.experimental.pallas as pl  # deferred so CPU-only processes never pay it
    from jax.experimental.pallas import tpu as pltpu

    # the framework enables x64 globally; Mosaic only legalizes i32 scalars
    with jax.enable_x64(False):
        (d, h), rows = w_gate.shape[1:], source.shape[0]
        hs = _slab(d, h, block_rows, x.dtype.itemsize, w_gate.dtype.itemsize)
        slabs = h // hs
        if diagnostics._enabled:  # trace time only: a trace of the path that took the kernel
            diagnostics.counter("kernels.gmm.fwd")
            if slabs > 1:
                diagnostics.counter("kernels.gmm.fwd.slabs")
        # a step sees its own and the next block's sources as one SMEM tile each
        seen = max(_SOURCE_TILE, block_rows)
        source = jnp.pad(source.astype(jnp.int32), (0, -rows % seen))
        per = seen // block_rows

        def last(used):  # past the used count: the last used block again
            return jnp.maximum(used[0] - 1, 0)

        def spec(shape, index, **kw):
            """A block of ``shape`` at ``index(block, slab, expert, used)``; on a grid of blocks
            alone (the whole expert fits) the slab is 0."""
            if slabs == 1:
                return pl.BlockSpec(shape, lambda i, expert, used: index(i, 0, expert, used), **kw)
            return pl.BlockSpec(shape, index, **kw)

        def slab(i, j, used):  # past the used count: the last used block's last slab
            return j if slabs == 1 else jnp.where(i < used[0], j, slabs - 1)

        need = _footprint(d, h, block_rows, x.dtype.itemsize, w_gate.dtype.itemsize, hs)
        padded = _token_tiles(d, x.dtype.itemsize)[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // block_rows,) if slabs == 1 else (rows // block_rows, slabs),
            in_specs=[
                spec((seen,), lambda i, j, e, u: (jnp.minimum(i, last(u)) // per,),
                     memory_space=pltpu.SMEM),
                spec((seen,), lambda i, j, e, u: (jnp.minimum(i + 1, last(u)) // per,),
                     memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),  # the tokens stay in HBM: rows come by DMA
                spec((1, d, hs), lambda i, j, e, u: (e[i], 0, slab(i, j, u))),
                spec((1, d, hs), lambda i, j, e, u: (e[i], 0, slab(i, j, u))),
                spec((1, hs, d), lambda i, j, e, u: (e[i], slab(i, j, u), 0)),
            ],
            out_specs=spec((block_rows * padded, _LANES),
                           lambda i, j, e, u: (jnp.minimum(i, last(u)), 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_rows * padded, _LANES), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ] + ([pltpu.VMEM((block_rows, d), jnp.float32)] if slabs > 1 else []),
        )
        return pl.pallas_call(
            functools.partial(_kernel, dtype=x.dtype, slabs=slabs,
                              br=sub or _row_chunk(block_rows, d, hs, w_gate.dtype.itemsize)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows * padded, _LANES), jnp.uint32),
            interpret=interpret,
            # the grid is a sequential sweep: a block's weights stay resident only because
            # its neighbour in the sweep is the same expert's, and its rows were fetched by
            # the step before
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * (1 if slabs == 1 else 2),
                vmem_limit_bytes=need + _VMEM_MARGIN),  # under _VMEM_CAP by the gate
            name="moe_grouped_fwd",
        )(block_expert, used, source, source, _words(x), w_gate, w_up, w_down)


def grouped_gated_silu(x, source, w_gate, w_up, w_down, block_expert, used, block_rows: int,
                       interpret: bool = False):
    """The sorted buffer's rows, ``x[source]`` with ``x`` (T, d) and ``source`` (rows,) int32
    in blocks of ``block_rows`` rows an expert, through the gated MLP of the expert each block
    belongs to: ``w_gate``, ``w_up`` (count, d, h) and ``w_down`` (count, h, d) stacked over the
    held experts, ``block_expert`` and ``used`` as :func:`block_map` gives them. Every entry
    of ``source`` names a token, padding too. Returns (rows, d) in ``x``'s type, **written
    only in its first** ``used`` **blocks**: what lies beyond is whatever the buffer held.
    Callers ask :func:`decline_reason` first. No gradient is defined on this entry."""
    return _grouped_pallas(x, source, w_gate, w_up, w_down, block_expert, used,
                           block_rows=block_rows, interpret=interpret)


def _combine_blocks(top_k: int) -> Tuple[int, int]:
    """``(tb, sb)``: the tokens of one grid step of the combine, whose ``top_k * tb`` pairs fill
    one SMEM tile of ``slot`` (128 tokens at top-8, 256 at top-4), and the tokens of one
    straight-line body inside it, which starts :data:`_CHUNK_ROWS` row DMAs."""
    tb = _SOURCE_TILE // top_k // 8 * 8
    return tb, min(tb, max(8, _CHUNK_ROWS // top_k // 8 * 8))


def _combine_footprint(d: int, top_k: int, itemsize: int) -> int:
    """Bytes of VMEM one grid step of the combine holds: the two blocks of gathered rows, the
    float32 output block and the lane-padded blocks of slots and weights, double-buffered."""
    tb = _combine_blocks(top_k)[0]
    return (2 * top_k * tb * _token_tiles(d, itemsize)[1] * _LANES * 4 + 2 * tb * d * 4
            + 2 * 2 * tb * _LANES * 4)


def _combine_kernel(held_ref, now_ref, next_ref, slot_ref, w_ref, ys_hbm, o_ref, buf, sem, *,
                    rows: int, sb: int, all_held: bool, dtype):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, n = pl.program_id(0), pl.num_programs(0)
    (tb, d), k = o_ref.shape, slot_ref.shape[1]
    tiles, padded = _token_tiles(d, jnp.dtype(dtype).itemsize)
    half = i % 2

    def fetch(row, pair, to):
        """Start the DMA of buffer row ``row`` into ``pair``'s place in gathered block ``to``."""
        pltpu.make_async_copy(
            ys_hbm.at[pl.ds(pl.multiple_of(row * padded, 8), padded), :],
            buf.at[to, pl.ds(pl.multiple_of(pair * padded, 8), padded), :], sem.at[to]).start()

    def fetch_block(src_ref, block, to):
        """A whole block's rows, from a rolled loop. Where every pair is held, entry ``at`` of
        the block's SMEM tile is pair ``at``'s row; else the tile lists the held pairs alone, as
        ``row * tile + pair``, and the loop ends with them: a pair held elsewhere costs nothing."""
        def one(at, carry):
            entry = src_ref[at]
            if all_held:
                fetch(entry, at, to)
            else:
                fetch(entry >> _SOURCE_TILE.bit_length() - 1, entry & _SOURCE_TILE - 1, to)
            return carry

        lax.fori_loop(0, k * tb if all_held else held_ref[block], one, 0)

    def wait(which, block):
        """Until the rows started into gathered block ``which`` for ``block`` have landed: one
        wait where every pair is held, else one for each binary digit of the held count (the
        rows' bytes add up, whichever rows they were)."""
        def rows_of(count):
            pltpu.make_async_copy(ys_hbm.at[pl.ds(0, count * padded), :],
                                  buf.at[which, pl.ds(0, count * padded), :], sem.at[which]).wait()

        if all_held:
            return rows_of(k * tb)
        held = held_ref[block]
        for bit in range((k * tb).bit_length()):
            pl.when((held >> bit) & 1 == 1)(functools.partial(rows_of, 1 << bit))

    def tokens(r0):
        # every pair held: these tokens' share of the next block's rows first, in program
        # order, so that the scalar core starts them under the vector work
        def one(p, carry):
            pair = lax.div(p, sb) * tb + r0 + lax.rem(p, sb)
            fetch(next_ref[pair], pair, 1 - half)
            return carry

        if all_held:
            lax.fori_loop(0, k * sb, one, 0, unroll=True)  # traced once, unrolled when lowered
        weights = w_ref[pl.ds(r0, sb), :]
        w = [jnp.broadcast_to(weights[:, j:j + 1], (sb, _LANES)) for j in range(k)]
        if not all_held:
            slots = slot_ref[pl.ds(r0, sb), :]
            held = [jnp.broadcast_to(slots[:, j:j + 1], (sb, _LANES)) < rows for j in range(k)]
        def lane_tile(s, carry):
            total = None
            for j in range(k):  # the sum runs over the ranks in their order, from the first
                words = buf[half, pl.ds((j * tb + r0) * padded + s, sb, stride=padded), :]
                if not all_held:  # selected, never multiplied: the row may hold anything
                    words = jnp.where(held[j], words, jnp.uint32(0))
                if jnp.dtype(dtype).itemsize == 4:
                    parts = (lax.bitcast_convert_type(words, jnp.float32) * w[j],)
                else:
                    parts = tuple(v * w[j] for v in _halves(words))
                total = parts if total is None else tuple(a + b for a, b in zip(total, parts))
            for c, part in enumerate(total):  # bfloat16: columns s.. and d / 2 + s..
                at = pl.multiple_of((c * tiles + s) * _LANES, _LANES)
                o_ref[pl.ds(r0, sb), pl.ds(at, _LANES)] = part
            return carry

        lax.fori_loop(0, tiles, lane_tile, 0)

    @pl.when(i == 0)
    def _first():  # nothing is in flight yet: the first block's rows, once a call
        fetch_block(now_ref, 0, 0)

    wait(half, i)
    if not all_held:  # the next block's held rows, before this block's sum
        fetch_block(next_ref, jnp.minimum(i + 1, n - 1), 1 - half)

    def body(r, carry):
        tokens(pl.multiple_of(r * sb, sb))
        return carry

    lax.fori_loop(0, tb // sb, body, 0)

    @pl.when(i + 1 == n)
    def _drain():  # the last step fetched its own block again: nothing stays in flight
        wait(1 - half, i)


@functools.partial(jax.jit, static_argnames=("d", "dtype", "all_held", "interpret"))
def _combine_pallas(ys, slot, w, d: int, dtype, all_held: bool, interpret: bool = False):
    import jax.experimental.pallas as pl  # deferred so CPU-only processes never pay it
    from jax.experimental.pallas import tpu as pltpu

    with jax.enable_x64(False):  # Mosaic only legalizes i32 scalars
        if diagnostics._enabled:  # trace time only: a trace of the path that took the kernel
            diagnostics.counter("kernels.gmm.combine")
        (t, k), itemsize = slot.shape, jnp.dtype(dtype).itemsize
        rows = ys.shape[0] // _token_tiles(d, itemsize)[1]
        tb, sb = _combine_blocks(k)
        n = -(-t // tb)
        # tokens past the end hold nothing: the form without a list needs whole blocks
        all_held = all_held and t % tb == 0
        slot = jnp.pad(slot.astype(jnp.int32), ((0, n * tb - t), (0, 0)), constant_values=rows)
        w = jnp.pad(w.astype(jnp.float32), ((0, n * tb - t), (0, 0)))
        # a step sees its own and the next block's slots as one SMEM tile each, ranks leading
        tile = jnp.pad(slot.reshape(n, tb, k).transpose(0, 2, 1).reshape(n, k * tb),
                       ((0, 0), (0, _SOURCE_TILE - k * tb)), constant_values=rows)
        held = jnp.sum(tile < rows, axis=1, dtype=jnp.int32)
        if not all_held:  # a block's held pairs first, each as ``row * tile + pair``
            at = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            tile = lax.sort(jnp.where(tile < rows, tile * _SOURCE_TILE + at,
                                      jnp.iinfo(jnp.int32).max), dimension=1)
        tile = tile.reshape(-1)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((_SOURCE_TILE,), lambda i, h: (i,), memory_space=pltpu.SMEM),
                pl.BlockSpec((_SOURCE_TILE,), lambda i, h: (jnp.minimum(i + 1, n - 1),),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tb, k), lambda i, h: (i, 0)),
                pl.BlockSpec((tb, k), lambda i, h: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # the buffer stays in HBM: rows come by DMA
            ],
            out_specs=pl.BlockSpec((tb, d), lambda i, h: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k * tb * _token_tiles(d, itemsize)[1], _LANES), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        y = pl.pallas_call(
            functools.partial(_combine_kernel, rows=rows, sb=sb, all_held=all_held, dtype=dtype),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n * tb, d), jnp.float32),
            interpret=interpret,
            # a sequential sweep: a step's rows were fetched by the step before
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_combine_footprint(d, k, itemsize) + _VMEM_MARGIN),
            name="moe_combine_fwd",
        )(held, tile, tile, slot, w, ys)
        return y[:t]


def combine(ys, slot, w, d: int, dtype, all_held: bool, interpret: bool = False):
    """``y[t] = sum_j w[t, j] * row(slot[t, j])`` (T, d) float32 over the ranks ``j`` whose pair
    is held here, ``slot[t, j] < rows``: ``ys`` is :func:`grouped_gated_silu`'s buffer of ``d``
    elements of ``dtype`` a row, ``slot`` (T, top_k) int32 the buffer row of each (token,
    expert) pair and ``w`` (T, top_k) float32 its weight. A row is fetched only for a pair held
    here, one DMA each, started a block of tokens ahead; a pair held elsewhere starts none and
    adds exactly 0, by selection. Products and sum are float32, **the sum in the order of the
    ranks**, ``(w_0 r_0 + w_1 r_1) + w_2 r_2 ...``: on a v5e bit for bit what XLA's sum over the
    leading axis of ``(k, T, d)`` gives (my chip runs, PR 36); a CPU contracts products into it.
    ``all_held`` states, when traced, that no pair is held elsewhere: the starts then need no
    list and one wait a block serves. Callers ask :func:`decline_reason` first. No gradient
    is defined on this entry."""
    return _combine_pallas(ys, slot, w, d=d, dtype=jnp.dtype(dtype), all_held=all_held,
                           interpret=interpret)
