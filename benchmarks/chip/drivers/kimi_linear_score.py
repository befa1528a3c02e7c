"""The ``kimi-linear-48b-a3b`` configuration's problem for ``solve_loop``: long-document
scoring through ``ht.nn.KimiLinear``.

One solve is one document of ``tokens`` ids through ``model(tokens)``, ended by reading back
the continuation's log-likelihood. The configuration's ``num_experts`` counts the experts held
here (``experts_held``); the router keeps the published width (``published.num_experts``).
Weights and the document come from the seed; the plain reference
(``reference_kimi_linear.py``, beside ``reference.py``) reads the same weights by name, is
given the same share of the experts and uses nothing that the program made.

**The selection bias is what balancing leaves.** Seeded weights route unevenly (the residual
stream of random layers shares a component, so a few experts draw several times their share)
and the share of a layer's pairs that lands on the held half then follows the seed, and the
time with it. A trained model's selection bias is what auxiliary-loss-free balancing left
(DeepSeek-V3: ``b_e += gamma sign(mean load - load_e)`` while it trains), so set-up runs that
rule through the plain reference, in float32, over a second document of
:data:`BALANCE_TOKENS` ids made from the seed, never the timed one: layer after layer, each
expert layer's bias takes :data:`BALANCE_ROUNDS` steps on the router's scores of the stream
that the layers before it (with their balanced biases) leave, the step shrinking from
:data:`BALANCE_STEP`. Program and reference then read the biases as every other weight.

**What the logits cannot see.** A KDA layer's error in bfloat16 (~0.5% of its output) hides a
kernel that holds the log-decay in the bounded kind's domain (a floor at -5 moves a layer's
output by ~0.1%), and the logits' gap is mostly tokens routed otherwise. So ``kda_rms_gap``
holds the program's KDA module in float32, where the kernel is exact to ~1e-6, to the
reference's token-by-token recurrence: every KDA layer's weights on the first
:data:`KDA_CHECK_TOKENS` positions of the document (RMS-normed embeddings), the largest gap of
the four.
"""

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import reference_kimi_linear as ref
from reference import rms_gap

FALLBACKS = ("fallback.nn.kda", "fallback.nn.mla")
BALANCE_TOKENS = 8192
BALANCE_ROUNDS = 64
BALANCE_STEP = 0.05  # the first round's step on the bias; each next one is 0.9 of it
KDA_CHECK_TOKENS = 4096
F32 = jnp.float32


def sign_rule(scores, bias, k: int):
    """The selection bias after :data:`BALANCE_ROUNDS` rounds of auxiliary-loss-free
    balancing on the router's ``scores`` (T, experts) float32: each round the top ``k`` of
    ``scores + bias`` a token and one step towards the mean load."""
    t, n = scores.shape
    mean = t * k / n

    def one(r, b):
        _, chosen = lax.top_k(scores + b, k)
        load = jnp.zeros(n, F32).at[chosen.reshape(-1)].add(1.0)
        return b + BALANCE_STEP * 0.9 ** r.astype(F32) * jnp.sign(mean - load)

    return lax.fori_loop(0, BALANCE_ROUNDS, one, bias)


@partial(jax.jit, static_argnames=("cfg_json", "index", "held"), donate_argnums=(1,))
def _balanced_layer(p, x, cfg_json: str, index: int, held):
    """``reference_kimi_linear.layer`` on the float32 stream ``x``, whose expert layer first
    balances its selection bias (:func:`sign_rule`) on its own router input. Returns (x, the
    bias in the parameter's type, or None)."""
    cfg = json.loads(cfg_json)
    eps = cfg["rms_norm_eps"]
    u = ref.rms_norm(x, p["attn_norm"]["weight"], eps)
    x = x + (ref.mla if ref.is_latent(cfg, index) else ref.kda)(p["attn"], u, cfg)
    m = ref.rms_norm(x, p["ffn_norm"]["weight"], eps)
    if "router" not in p["ffn"]:
        return x + ref.gated_mlp(p["ffn"], m), None
    bias = p["ffn"]["router_bias"]
    scores = jax.nn.sigmoid(ref._mm(m, p["ffn"]["router"]))  # reference_kimi_linear.route's
    bias = sign_rule(scores, bias.astype(F32), cfg["num_experts_per_token"]).astype(bias.dtype)
    y, _ = ref.moe(dict(p["ffn"], router_bias=bias), m, cfg, held)
    return x + y, bias


class KimiLinearScore:
    def __init__(self, config: dict, seed: int):
        import heat_tpu as ht

        if not hasattr(ht.nn, "KimiLinear"):
            raise SystemExit("this tree has no ht.nn.KimiLinear: the cell cannot run on it")
        self.cfg = dict(config, num_experts=config["published"]["num_experts"])
        self.held = tuple(config["experts_held"])
        key = jax.random.key(seed, impl="rbg")
        self.model = ht.nn.KimiLinear(self.cfg, continuation=config["continuation"],
                                      experts_held=self.held, dtype=config["dtype"])
        # every KDA layer is built alike: one of them runs the float32 check on each's weights
        self.kda = next(b.attn for i, b in enumerate(self.model.layers)
                        if not ref.is_latent(self.cfg, i))
        # one program makes every weight on the device; the driver keeps its own handle on
        # the pytree, so a model whose parameters were swapped is still compared with these
        params = jax.jit(self.model.init)(jax.random.fold_in(key, 0))
        document = jax.random.randint(jax.random.fold_in(key, 2), (BALANCE_TOKENS,), 0,
                                      config["vocab_size"], jnp.int32)
        self.params = self.model.params = self._balanced(params, document)
        self.tokens = jax.random.randint(jax.random.fold_in(key, 1), (config["tokens"],), 0,
                                         config["vocab_size"], jnp.int32)
        self.fallbacks = self._attention_fallbacks(ht)  # before anything traces the program
        self.out = None

    def _balanced(self, params, document):
        """``params`` with every expert layer's selection bias balanced over ``document``
        through the plain reference (:func:`_balanced_layer`, one layer after another)."""
        key = json.dumps(self.cfg, sort_keys=True)
        x = params["embed"]["weight"][document].astype(F32)
        layers = []
        for index, p in enumerate(params["layers"]):
            x, bias = _balanced_layer(p, x, key, index, self.held)
            if bias is not None:
                p = dict(p, ffn=dict(p["ffn"], router_bias=bias))
            layers.append(p)
        return dict(params, layers=layers)

    def _attention_fallbacks(self, ht) -> int:
        """Token-mixing layers of the program whose core is the plain path and not its
        kernel: ``fallback.nn.kda`` + ``fallback.nn.mla``, which the program counts while
        it is traced. The trace is made here, abstractly (nothing compiles or runs), with
        diagnostics on; the first solve finds it made."""
        was_on = ht.diagnostics.enabled()
        ht.diagnostics.enable()
        try:
            def count():
                counters = ht.diagnostics.report()["counters"]
                return sum(counters.get(name, 0) for name in FALLBACKS)

            before = count()
            jax.eval_shape(self.model._program, self.params, self.tokens)
            return count() - before
        finally:
            if not was_on:
                ht.diagnostics.disable()

    def solve(self):
        out = self.model(self.tokens)
        with jax.profiler.TraceAnnotation("bench.readback"):
            self.model.readback(out)  # the log-likelihood on the host: ends the solve
        self.out = out

    def release(self):
        out = self.out
        # the scalar that was read back is not compared, as in the other scoring cells: a
        # sum of 128 log-probabilities hides errors of both signs (PERF.md)
        self.got = {"logits": out.logits, "routes": list(out.chosen)}
        self.out = self.model = None

    def _kda_rms_gap(self, precision: str) -> float:
        """The largest rms gap over the KDA layers between their token mixing and the
        reference's in float32, on the first :data:`KDA_CHECK_TOKENS` positions: the
        program's module in float32 (or the reference at ``precision``, the control)."""
        cfg, eps = self.cfg, self.cfg["rms_norm_eps"]
        embed = self.params["embed"]["weight"][self.tokens[:KDA_CHECK_TOKENS]]
        want_of = jax.jit(lambda p, u: ref.kda(p, u, cfg))
        if precision == "float32":
            got_of = jax.jit(lambda p, u: self.kda.apply(jax.tree.map(lambda a: a.astype(F32), p),
                                                         u))
        else:
            got_of = jax.jit(lambda p, u: ref.kda(p, u, cfg, precision))
        worst = 0.0
        for index, p in enumerate(self.params["layers"]):
            if ref.is_latent(cfg, index):
                continue
            u = ref.rms_norm(embed, p["attn_norm"]["weight"], eps)
            worst = max(worst, rms_gap(got_of(p["attn"], u), want_of(p["attn"], u)))
        return worst

    def compare(self, precision: str) -> dict:
        def forward(p):
            return ref.forward(self.params, self.tokens, self.cfg, self.cfg["continuation"], p,
                               self.held)

        ref_out = forward("float32")
        got = self.got if precision == "float32" else forward(precision)

        differ = rows = 0
        for mine, theirs in zip(got["routes"], ref_out["routes"]):
            mine, theirs = np.sort(np.asarray(mine), axis=1), np.sort(np.asarray(theirs), axis=1)
            differ += int((mine != theirs).any(axis=1).sum())
            rows += theirs.shape[0]
        return {
            "logits_rms_gap": rms_gap(got["logits"], ref_out["logits"]),
            "route_mismatch_share": differ / rows,
            "attention_fallbacks": float(self.fallbacks),
            "kda_rms_gap": self._kda_rms_gap(precision),
        }
