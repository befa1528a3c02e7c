"""Grouped gated-SiLU products over an expert-sorted, block-padded buffer (Pallas, TPU).

``nn/moe.py`` sorts its (token, expert) pairs by expert and pads every expert's group to
whole blocks of ``block_rows`` rows, so a block of the buffer belongs to exactly one
expert and no group edge lies inside a block. The kernel's grid is the buffer's blocks.
Scalar-prefetched, it is told which expert each block belongs to and how many blocks are
used; the weights' ``BlockSpec`` index maps read the expert from that map, so an expert's
three matrices are fetched when the expert changes (the pipeline fetches the next
expert's under the current expert's last block) and stay in VMEM over its blocks. A
block past the used count does no work, and its index maps repeat the last used block's,
so it fetches and writes nothing: its rows of the output are **never written**. Whoever
reads the output selects on what it wrote; it never multiplies an unwritten row by 0.

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
body) and drains it before the kernel ends.

**One step** is ``W_down (silu(W_gate x) * W_up x)`` on one block of ``block_rows`` rows, in
the numerical form of :func:`heat_tpu.nn.modules.gated_silu`: operands as they are
stored, float32 accumulation of gate and up, ``silu(gate) * up`` in float32, rounded to
the activation type, float32 accumulation of the down product, rounded once. 16-bit
operands state ``Precision.DEFAULT`` (one MXU pass, whatever the process-wide default
says: Mosaic refuses them at "highest"), float32 operands ``Precision.HIGHEST``. The
hidden activation ``(block_rows, h)`` stays in VMEM and never goes through HBM.

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

**VMEM.** Whole experts are resident, in both pipeline buffers: at ``d`` 2048, ``h`` 1024
in bfloat16 (Trinity-Mini) 2 x 12.6 MB of weights, at ``d`` 3584 (Xing4.0) 2 x 22 MB.
:func:`_footprint` counts a step's bytes from the shapes, the call raises Mosaic's
``vmem_limit_bytes`` to that count plus a margin, and :func:`decline_reason` declines what
would pass :data:`_VMEM_CAP` of the v5e's 128 MiB before Mosaic does.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import diagnostics

__all__ = ["grouped_gated_silu", "available", "decline_reason", "block_map"]

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


def _footprint(d: int, h: int, block_rows: int, x_size: int, w_size: int) -> int:
    """Bytes of VMEM one grid step holds: the three weight matrices of one expert and the
    ``(block_rows, d)`` output block, double-buffered; the two gathered blocks of padded
    32-bit rows; and the live tiles of a row chunk: its tokens unpacked, gate and up in
    float32, the hidden activation, the down product."""
    br = _row_chunk(block_rows, d, h, w_size)
    weights = 2 * 3 * d * h * w_size
    blocks = 2 * block_rows * (d * x_size + _token_tiles(d, x_size)[1] * _LANES * 4)
    tiles = br * (x_size * d + 2 * 4 * h + x_size * h + 4 * d)
    return weights + blocks + tiles


def available(interpret: bool = False) -> bool:
    """Whether the kernel can run here: on a TPU backend, or interpreted anywhere."""
    return interpret or jax.default_backend() == "tpu"


def decline_reason(x, rows: int, w_gate, w_down, block_rows: int) -> Optional[str]:
    """Why the kernel is not compiled for tokens ``x`` (T, d) behind a sorted buffer of
    ``rows`` rows against stacked ``w_gate`` (count, d, h) and ``w_down`` (count, h, d), or
    ``None`` where it is: the gate says no before Mosaic does."""
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
    need = _footprint(d, h, block_rows, x.dtype.itemsize, w_gate.dtype.itemsize)
    if need + _VMEM_MARGIN > _VMEM_CAP:
        return (f"VMEM: an expert of d={d}, h={h} in {w_gate.dtype} with blocks of {block_rows} "
                f"rows holds {need >> 20} MiB of {_VMEM_CAP >> 20}")
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


def _kernel(expert_ref, used_ref, now_ref, next_ref, x_hbm, wg_ref, wu_ref, wd_ref, o_ref,
            buf, sem, *, br: int, dtype):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, used = pl.program_id(0), used_ref[0]
    rows, d = o_ref.shape
    tiles, padded = _token_tiles(d, jnp.dtype(dtype).itemsize)
    per = now_ref.shape[0] // rows  # blocks whose sources one SMEM tile holds
    slot = i % 2
    # 16-bit operands are one MXU pass whatever the process-wide default says (Mosaic
    # refuses them at "highest"); float32 operands multiply exactly, as `contract` does
    precision = lax.Precision.DEFAULT if wg_ref.dtype.itemsize < 4 else lax.Precision.HIGHEST

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

        def half(bits):  # a bfloat16's bits in the top half of a float32 are its value
            return lax.bitcast_convert_type(bits, jnp.float32).astype(dtype)

        return jnp.concatenate([half(w << 16) for w in words]
                               + [half(w & jnp.uint32(0xFFFF0000)) for w in words], axis=1)

    def dot(a, b):
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32, precision=precision)

    def chunk(r0):
        # the next block's rows first, in program order: their DMA starts are scalar work
        # that the scheduler places under this chunk's products
        following = jnp.minimum(i + 1, used - 1)

        def one(row, carry):
            fetch(next_ref, following, r0 + row, 1 - slot)
            return carry

        lax.fori_loop(0, br, one, 0, unroll=True)  # traced once, unrolled when lowered
        x = tokens(r0)
        gate, up = dot(x, wg_ref[0]), dot(x, wu_ref[0])
        hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        o_ref[pl.ds(r0, br), :] = dot(hidden, wd_ref[0]).astype(o_ref.dtype)

    @pl.when((i == 0) & (used > 0))
    def _first():  # nothing is in flight yet: the first block's rows, once a call
        def one(row, carry):
            fetch(now_ref, 0, row, 0)
            return carry

        lax.fori_loop(0, rows, one, 0)

    @pl.when(i < used)
    def _block():
        wait(slot)
        if br == rows:
            chunk(0)
        else:  # rolled: the body is compiled once (its size is what the chunk was cut for)
            def body(r, carry):
                chunk(pl.multiple_of(r * br, br))
                return carry

            lax.fori_loop(0, rows // br, body, 0)

    @pl.when(i + 1 == used)
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
        if diagnostics._enabled:  # trace time only: a trace of the path that took the kernel
            diagnostics.counter("kernels.gmm.fwd")
        # a step sees its own and the next block's sources as one SMEM tile each
        seen = max(_SOURCE_TILE, block_rows)
        source = jnp.pad(source.astype(jnp.int32), (0, -rows % seen))
        per = seen // block_rows

        def last(used):  # past the used count: the last used block again
            return jnp.maximum(used[0] - 1, 0)

        def out_block(i, expert, used):
            return jnp.minimum(i, last(used)), 0

        def of_expert(i, expert, used):
            return expert[i], 0, 0

        need = _footprint(d, h, block_rows, x.dtype.itemsize, w_gate.dtype.itemsize)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // block_rows,),
            in_specs=[
                pl.BlockSpec((seen,), lambda i, e, u: (jnp.minimum(i, last(u)) // per,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((seen,), lambda i, e, u: (jnp.minimum(i + 1, last(u)) // per,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),  # the tokens stay in HBM: rows come by DMA
                pl.BlockSpec((1, d, h), of_expert),
                pl.BlockSpec((1, d, h), of_expert),
                pl.BlockSpec((1, h, d), of_expert),
            ],
            out_specs=pl.BlockSpec((block_rows, d), out_block),
            scratch_shapes=[
                pltpu.VMEM((2, block_rows * _token_tiles(d, x.dtype.itemsize)[1], _LANES),
                           jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        return pl.pallas_call(
            functools.partial(_kernel, dtype=x.dtype,
                              br=sub or _row_chunk(block_rows, d, h, w_gate.dtype.itemsize)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
            interpret=interpret,
            # the grid is a sequential sweep: a block's weights stay resident only because
            # its neighbour in the sweep is the same expert's, and its rows were fetched by
            # the step before
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
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
