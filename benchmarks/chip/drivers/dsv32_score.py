"""The ``deepseek-v3.2-exp`` configuration's problem for ``solve_loop``: long-document
scoring through ``ht.nn.DeepseekV32``.

One solve is one document of ``tokens`` ids through ``model(tokens)``, ended by reading back
the continuation's log-likelihood. The configuration's ``n_routed_experts`` counts the
experts held here (``experts_held``); the router keeps the published width
(``published.n_routed_experts``). Weights and the document come from the seed; the plain
reference (``reference_deepseek_v32.py``, beside ``reference.py``) reads the same weights by
name, is given the same share of the experts and uses nothing that the program made.
"""

import jax
import jax.numpy as jnp
import numpy as np

FALLBACKS = ("fallback.nn.dsa", "fallback.nn.mla")


class Dsv32Score:
    def __init__(self, config: dict, seed: int):
        import heat_tpu as ht

        if not hasattr(ht.nn, "DeepseekV32"):
            raise SystemExit("this tree has no ht.nn.DeepseekV32: the cell cannot run on it")
        self.cfg = dict(config, n_routed_experts=config["published"]["n_routed_experts"])
        self.held = tuple(config["experts_held"])
        key = jax.random.key(seed, impl="rbg")
        self.model = ht.nn.DeepseekV32(
            self.cfg, continuation=config["continuation"], experts_held=self.held,
            dtype=config["dtype"], head_groups=config["head_groups"],
            ffn_pieces=config["ffn_pieces"])
        # one program makes every weight on the device; the driver keeps its own handle on
        # the pytree, so a model whose parameters were swapped is still compared with these
        self.params = jax.jit(self.model.init)(jax.random.fold_in(key, 0))
        self.model.params = self.params
        self.tokens = jax.random.randint(jax.random.fold_in(key, 1), (config["tokens"],), 0,
                                         config["vocab_size"], jnp.int32)
        self.sample = self.model.sampled_queries(config["tokens"])
        self.fallbacks = self._attention_fallbacks(ht)
        self.out = None

    def _attention_fallbacks(self, ht) -> int:
        """Layers of the program whose index or whose attention core is the plain path and
        not its kernel: ``fallback.nn.dsa`` + ``fallback.nn.mla``, which the program counts
        while it is traced. The trace is made here, abstractly (nothing compiles or runs),
        with diagnostics on; the first solve finds it made."""
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
        from heat_tpu.core.kernels.sparse_index import unpack_mask

        out = self.out
        # the scalar that was read back is not compared, as in the other scoring cells: a
        # sum of 128 log-probabilities hides errors of both signs (PERF.md)
        self.got = {"logits": out.logits, "routes": list(out.chosen),
                    "selections": [np.asarray(unpack_mask(words, self.cfg["tokens"]))
                                   for words in out.selected]}
        self.out = self.model = None

    def compare(self, precision: str) -> dict:
        import reference_deepseek_v32

        from reference import rms_gap

        def forward(p):
            return reference_deepseek_v32.forward(self.params, self.tokens, self.cfg,
                                                  self.cfg["continuation"], p, self.held,
                                                  self.sample)

        ref = forward("float32")
        got = self.got if precision == "float32" else forward(precision)

        differ = rows = 0
        for mine, theirs in zip(got["routes"], ref["routes"]):
            mine, theirs = np.sort(np.asarray(mine), axis=1), np.sort(np.asarray(theirs), axis=1)
            differ += int((mine != theirs).any(axis=1).sum())
            rows += theirs.shape[0]
        # of the keys the reference keeps for a sampled query, the share the program lacks
        missed = [1.0 - (np.asarray(mine) & theirs).sum(axis=1) / theirs.sum(axis=1)
                  for mine, theirs in zip(got["selections"], map(np.asarray, ref["selections"]))]
        return {
            "logits_rms_gap": rms_gap(got["logits"], ref["logits"]),
            "route_mismatch_share": differ / rows,
            "select_mismatch_share": float(np.mean(missed)),
            "attention_fallbacks": float(self.fallbacks),
        }
