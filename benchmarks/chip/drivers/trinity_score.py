"""The ``trinity-mini-26b-a3b`` configuration's problem for ``solve_loop``: long-document
scoring through ``ht.nn.Trinity``.

One solve is one document of ``tokens`` ids through ``model(tokens)``, ended by reading
back the continuation's log-likelihood. Weights and the document come from the seed; the
plain reference (``reference_trinity.py``, beside ``reference.py``) reads the same weights
by name and uses nothing else that the program made.
"""

import jax
import jax.numpy as jnp
import numpy as np


class TrinityScore:
    def __init__(self, config: dict, seed: int):
        import heat_tpu as ht

        if not hasattr(ht.nn, "Trinity"):
            raise SystemExit("this tree has no ht.nn.Trinity: the cell cannot run on it")
        self.cfg = config
        key = jax.random.key(seed, impl="rbg")
        self.model = ht.nn.Trinity(config, continuation=config["continuation"],
                                   dtype=config["dtype"])
        # one program makes every weight on the device; the driver keeps its own handle on
        # the pytree, so a model whose parameters were swapped is still compared with these
        self.params = jax.jit(self.model.init)(jax.random.fold_in(key, 0))
        self.model.params = self.params
        self.tokens = jax.random.randint(jax.random.fold_in(key, 1), (config["tokens"],), 0,
                                         config["vocab_size"], jnp.int32)
        self.fallbacks = self._attention_fallbacks(ht)
        self.out = None

    def _attention_fallbacks(self, ht) -> int:
        """Attention layers of the program whose core is the XLA path and not the flash
        kernel: ``fallback.nn.gqa``, which the program counts while it is traced. The
        trace is made here, abstractly (nothing compiles or runs), with diagnostics on;
        the first solve finds it made."""
        was_on = ht.diagnostics.enabled()
        ht.diagnostics.enable()
        try:
            def count():
                return ht.diagnostics.report()["counters"].get("fallback.nn.gqa", 0)

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
        # the scalar that was read back is not compared, as in the xing4 cell: a sum of 128
        # log-probabilities hides errors of both signs (PERF.md)
        self.got = {"logits": out.logits, "routes": list(out.chosen)}
        self.out = self.model = None

    def compare(self, precision: str) -> dict:
        import reference_trinity

        from reference import rms_gap

        def forward(p):
            return reference_trinity.forward(self.params, self.tokens, self.cfg,
                                             self.cfg["continuation"], p)

        ref = forward("float32")
        got = self.got if precision == "float32" else forward(precision)

        differ = rows = 0
        for mine, theirs in zip(got["routes"], ref["routes"]):
            mine, theirs = np.sort(np.asarray(mine), axis=1), np.sort(np.asarray(theirs), axis=1)
            differ += int((mine != theirs).any(axis=1).sum())
            rows += theirs.shape[0]
        return {
            "logits_rms_gap": rms_gap(got["logits"], ref["logits"]),
            "route_mismatch_share": differ / rows,
            "attention_fallbacks": float(self.fallbacks),
        }
