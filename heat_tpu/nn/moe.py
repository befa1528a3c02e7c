"""Token-routed experts: a sigmoid router with a selection bias, top-k without dropped
tokens, a grouped product over the experts held here, and a shared expert.

For every token ``u`` (d)::

    s      = sigmoid(u W_g)                 float32, over all E experts
    chosen = top_k(s + b)                   b: selection bias (auxiliary-loss-free
                                            balancing: it moves the choice, not the weight)
                                            with ``n_group`` > 1: among the experts of the
                                            ``topk_group`` best groups only (see below)
    w      = s[chosen] / sum(s[chosen]) * scaling
    y      = sum_e w_e W_down,e (silu(W_gate,e u) * W_up,e u)  +  shared(u)

**The group limit.** With ``n_group`` > 1 the experts are ``n_group`` groups of
neighbours, a group's score is the sum of its two largest ``s + b``, the ``topk_group``
best groups stay and the top k are taken among their experts alone: a token's experts then
lie on at most ``topk_group`` of the ``n_group`` holders. ``n_group = 1`` is no limit and
traces none of this.

**Which experts live here.** ``experts_held = (first, count)`` states what the one-integer
``split`` of a DNDarray cannot: this instance holds the weights of experts
``first .. first + count - 1`` of ``n_experts``. It routes over all ``n_experts``, computes
its own experts' part of ``y`` for the tokens routed to them, and adds the shared expert
(which every holder computes alike). What the other holders' experts would add is left
out: summing the parts over all holders, with the shared expert counted once, gives the
uncut layer. On one chip the layer runs without its exchange; nothing stands in for the
absent chips.

**No token is dropped.** The (token, expert) pairs are laid out by expert, then by token,
and every expert's group is padded to whole blocks of ``block_rows`` rows, so the buffer
holds any imbalance (``T k + count * block_rows`` rows) and a block belongs to exactly one
expert. Only the blocks in use are multiplied: the cost follows the tokens, not a capacity.
The order is found by counting, not by sorting: a token's experts are distinct, so a pair's
place in its expert's group is the number of earlier tokens that chose the expert, a
running count along a 0/1 matrix (held experts, tokens); the groups' first rows follow from
its row sums, and a pair reads its row, as the router reads a chosen score, by comparison
against the experts' indices. XLA's sort, and its gathers and scatters of single elements,
move one element at a time on a TPU; the one scatter left writes ``source``.

**The products.** On a TPU the held experts' gated products are one grouped Pallas call
over the sorted buffer (``core/kernels/grouped_matmul.py``, ``moe_grouped_fwd`` in a device
trace): its grid is the buffer's blocks, a prefetched map names each block's expert, an
expert's weights stay on the chip over its blocks, and the rows come through ``source`` by
DMA, so the buffer's input side is never written to HBM. It writes the blocks in use and
nothing else; a padding row reads some token's row, since no pair's slot points at it.

**The combine.** A token's ``y`` is the weighted sum of its pairs' rows of that buffer. On a
TPU it is the second Pallas call of the same module (``moe_combine_fwd``): the buffer stays
in the 32-bit words the grouped kernel reads its input in, so a row is one aligned DMA; a
step fetches the rows of the next 1,024 pairs, **those held here only**, and sums the current
ones rank by rank in float32 from VMEM, so neither a gathered row a pair nor a ``(k, T, d)``
intermediate goes through HBM. Where the kernels' one gate declines (another backend, another
type, widths or ``block_rows`` off the tiles, no slab of an expert fits VMEM) the same blocks
go through a ``jnp`` loop over the held experts and the combine is a gather and a sum, and
``record_fallback("nn.moe", why)`` says why. Either way a pair held elsewhere contributes
exactly 0, by a select and never by a product with 0.

No reference counterpart (the reference has no expert layers).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import diagnostics
from ..core.kernels import grouped_matmul
from .modules import GatedMLP, Module, contract, gated_silu, normal_weight

__all__ = ["MoE"]


def _read(table, index, axis):
    """``table`` read along ``axis``, its experts' axis, at ``index``: ``table[t, index[t, j]]``
    of a ``table`` (T, n) at ``index`` (T, k) for ``axis`` 1, ``table[index[j, t], t]`` of (n, T)
    at (k, T) for ``axis`` 0. A compare-select-reduce against the experts' indices and not a
    gather, which on a TPU moves one element at a time; with the tokens last (``axis`` 0) the
    reduction is element-wise adds and costs a tenth of one across the lanes. ``table`` has 32
    bits an element and the sum runs over them as integers, so it is the selected element
    exactly in whatever order it is taken (a float sum could be merged with a caller's own and
    reordered); an index outside ``0 .. n - 1`` reads 0."""
    shape = index.shape[:axis + 1] + table.shape[axis:axis + 1] + index.shape[axis + 1:]
    hit = jnp.expand_dims(index, axis + 1) == lax.broadcasted_iota(jnp.int32, shape, axis + 1)
    bits = jnp.expand_dims(lax.bitcast_convert_type(table, jnp.int32), axis)
    picked = jnp.sum(jnp.where(hit, bits, 0), axis=axis + 1, dtype=jnp.int32)
    return lax.bitcast_convert_type(picked, table.dtype)


def _count_before(held):
    """The exclusive running count along the rows of a 0/1 matrix (n, T) int32, on the MXU:
    inside chunks of 512 columns a product with a 0/1 triangle (bfloat16 operands, float32
    sums: exact), plus the running count of the chunks' totals."""
    n, t = held.shape
    chunk = 512
    h = jnp.pad(held, ((0, 0), (0, -t % chunk))).reshape(n, -1, chunk)
    earlier = jnp.arange(chunk)[:, None] < jnp.arange(chunk)[None, :]
    within = jnp.einsum("nci,ij->ncj", h.astype(jnp.bfloat16), earlier.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32, precision=lax.Precision.DEFAULT)
    total = jnp.sum(h, axis=2, dtype=jnp.int32)
    ahead = jnp.cumsum(total, axis=1, dtype=jnp.int32) - total
    return (within.astype(jnp.int32) + ahead[:, :, None]).reshape(n, -1)[:, :t]


class MoE(Module):
    """Routed gated-SiLU experts plus ``n_shared`` shared experts (one gated MLP of
    ``n_shared * hidden``), on tokens ``(T, dim)``. ``apply`` returns
    ``(y, {"chosen": (T, k) int32, "load": (count,) int32})``: the router's choices over
    all experts and the rows each held expert multiplied. ``n_group`` and ``topk_group``
    limit the groups a token may choose from (none by default).

    Expert weights are stored stacked, ``(count, dim, hidden)`` and ``(count, hidden,
    dim)``, in ``dtype``; the router's weight and its selection bias are float32.
    """

    def __init__(self, dim: int, hidden: int, n_experts: int, top_k: int, n_shared: int = 1,
                 scaling: float = 1.0, experts_held: Optional[Tuple[int, int]] = None,
                 block_rows: int = 512, dtype=jnp.float32, n_group: int = 1,
                 topk_group: int = 1):
        first, count = experts_held if experts_held is not None else (0, n_experts)
        if first < 0 or count < 1 or first + count > n_experts:
            raise ValueError(f"experts_held {experts_held} lies outside 0..{n_experts}")
        if n_group > 1 and (n_experts % n_group or not 1 <= topk_group <= n_group
                            or topk_group * (n_experts // n_group) < top_k
                            or n_experts // n_group < 2):
            raise ValueError(f"{n_experts} experts in {n_group} groups of which {topk_group} "
                             f"stay do not hold a token's {top_k} (a group has at least 2)")
        self.n_group, self.topk_group = n_group, topk_group
        self.dim, self.hidden = dim, hidden
        self.n_experts, self.top_k = n_experts, top_k
        self.scaling = scaling
        self.first, self.count = first, count
        self.block_rows = block_rows
        self.dtype = jnp.dtype(dtype)
        self.shared = GatedMLP(dim, n_shared * hidden, dtype) if n_shared else None

    def init(self, key):
        k_router, k_bias, k_gate, k_up, k_down, k_shared = jax.random.split(key, 6)
        e, d, h, dt = self.count, self.dim, self.hidden, self.dtype
        params = {
            "router": jax.random.normal(k_router, (d, self.n_experts), jnp.float32)
            * jnp.float32(d ** -0.5),
            # a selection bias as balancing would leave it: small against the scores' spread
            "router_bias": 0.05 * jax.random.normal(k_bias, (self.n_experts,), jnp.float32),
            "experts": {
                "w_gate": normal_weight(k_gate, (e, d, h), dt, d ** -0.5),
                "w_up": normal_weight(k_up, (e, d, h), dt, d ** -0.5),
                "w_down": normal_weight(k_down, (e, h, d), dt, h ** -0.5),
            },
        }
        if self.shared is not None:
            params["shared"] = self.shared.init(k_shared)
        return params

    def route(self, params, u):
        """``(chosen (T, k) int32, weights (T, k) float32)`` over all experts."""
        scores = jax.nn.sigmoid(contract("td,de->te", u, params["router"]))
        choice = scores + params["router_bias"]
        if self.n_group > 1:
            by_group = choice.reshape(choice.shape[0], self.n_group, -1)
            group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
            _, kept = lax.top_k(group_score, self.topk_group)
            stays = jnp.any(kept[:, :, None] == jnp.arange(self.n_group, dtype=kept.dtype),
                            axis=1)
            choice = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(choice.shape)
        chosen = lax.top_k(choice, self.top_k)[1].astype(jnp.int32)
        # read with k last, as a gather would leave the scores: a float sum takes its order
        # from its operand's shape, and the sum below is held to the gathered form's bits
        w = _read(scores, chosen, 1)
        w = w / jnp.sum(w, axis=1, keepdims=True) * jnp.float32(self.scaling)
        return chosen, w

    def _layout(self, chosen):
        """Where each (token, expert) pair goes in the padded, expert-ordered buffer.
        Returns ``slot`` (T k,) int32 (pairs of experts held elsewhere: the buffer's
        length, which reads as nothing and writes nowhere), the token behind each buffer
        row ``source`` (rows,) (padding: token 0, whose product no slot reads), each held
        expert's first row and its number of blocks, and the load (count,)."""
        t, k = chosen.shape
        b, e = self.block_rows, self.count
        rows = -(-t * k // b) * b + e * b
        local = chosen.T - jnp.int32(self.first)  # (k, T); outside 0..e-1: held elsewhere
        held_by = jnp.sum(local[:, None, :] == jnp.arange(e, dtype=jnp.int32)[:, None], axis=0,
                          dtype=jnp.int32)
        load = jnp.sum(held_by, axis=1, dtype=jnp.int32)
        blocks = (load + (b - 1)) // b
        first_row = jnp.cumsum(blocks * b, dtype=jnp.int32) - blocks * b
        # a token's experts are distinct, so a pair's rank in its expert's group is the
        # number of earlier tokens that chose the expert
        slot = _read(first_row[:, None] + _count_before(held_by), local, 0)
        slot = jnp.where((local >= 0) & (local < e), slot, jnp.int32(rows))
        token = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (k, t))
        source = jnp.zeros((rows,), jnp.int32).at[slot.reshape(-1)].set(token.reshape(-1),
                                                                       mode="drop")
        slot = slot.T.reshape(-1)
        return slot, source, first_row, blocks, load

    def _loop(self, experts, x, source, first_row, blocks):
        """The sorted buffer ``x[source]`` (rows, dim): every held expert's blocks through
        its gated MLP, one expert and one block at a time. Rows of blocks not in use are 0."""
        b = self.block_rows
        xs = jnp.take(x, source, axis=0, mode="clip")

        def one_expert(e, ys):
            weights = [lax.dynamic_index_in_dim(experts[name], e, 0, False)
                       for name in ("w_gate", "w_up", "w_down")]

            def one_block(i, ys):
                row = first_row[e] + i * b
                y = gated_silu(lax.dynamic_slice_in_dim(xs, row, b, 0), *weights)
                return lax.dynamic_update_slice_in_dim(ys, y, row, 0)

            return lax.fori_loop(0, blocks[e], one_block, ys)

        return lax.fori_loop(0, self.count, one_expert, jnp.zeros_like(xs))

    def _routed(self, experts, x, w, slot, source, first_row, blocks):
        """The held experts' part of ``y`` (T, dim) float32: the sorted buffer through the
        experts, and each token's weighted sum over its pairs held here. Both halves are the
        kernels' or both are ``jnp``: the buffer's layout ties them."""
        t, k = w.shape
        b, rows = self.block_rows, source.shape[0]
        why = (grouped_matmul.decline_reason(x, rows, experts["w_gate"], experts["w_down"], b, k)
               if grouped_matmul.available() else f"backend {jax.default_backend()}")
        if why is None:
            ys = grouped_matmul.grouped_gated_silu(
                x, source, experts["w_gate"], experts["w_up"], experts["w_down"],
                *grouped_matmul.block_map(blocks, rows // b), block_rows=b)
            return grouped_matmul.combine(ys, slot.reshape(t, k), w, self.dim, x.dtype,
                                          all_held=self.count == self.n_experts)
        diagnostics.record_fallback("nn.moe", why)
        ys = self._loop(experts, x, source, first_row, blocks)
        # a pair held elsewhere adds exactly 0: selected, since the row it would read may
        # never have been written. Pairs are taken rank-major, (k, T), so that the
        # weighted sum runs over the leading axis and not over a sublane-padded k
        slot = slot.reshape(t, k).T.reshape(-1)
        held = slot < rows
        picked = jnp.where(held[:, None], ys[jnp.where(held, slot, 0)], 0)
        picked = picked.reshape(k, t, self.dim)
        return jnp.sum(picked.astype(jnp.float32) * w.T[:, :, None], axis=0)

    def apply(self, params, x, *, key=None, train=False):
        if x.ndim != 2:
            raise ValueError(f"MoE routes tokens of shape (T, dim); got {x.shape}")
        with jax.named_scope("ht.nn.moe"):
            chosen, w = self.route(params, x)
            slot, source, first_row, blocks, load = self._layout(chosen)
            y = self._routed(params["experts"], x, w, slot, source, first_row, blocks)
            if self.shared is not None:
                y = y + self.shared.apply(params["shared"], x).astype(jnp.float32)
            return y.astype(x.dtype), {"chosen": chosen, "load": load}
