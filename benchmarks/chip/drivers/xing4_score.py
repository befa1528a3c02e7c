"""The ``xing4.0-29b-a4b`` configuration's problem for ``solve_loop``: long-document
scoring through ``ht.nn.Xing4``.

One solve is one document of ``tokens`` ids through ``model(tokens)``, ended by reading
back the continuation's log-likelihood under the main head and under the
multi-token-prediction head. Weights and the document come from the seed; the plain
reference (``reference_xing4.py``, beside ``reference.py``) reads the same weights by
name and uses nothing else that the program made.
"""

import jax
import jax.numpy as jnp
import numpy as np


class Xing4Score:
    def __init__(self, config: dict, seed: int):
        import heat_tpu as ht

        self.cfg = config
        key = jax.random.key(seed, impl="rbg")
        self.model = ht.nn.Xing4(config, continuation=config["continuation"],
                                 dtype=config["dtype"])
        # one program makes every weight on the device; the driver keeps its own handle on
        # the pytree, so a model whose parameters were swapped is still compared with these
        self.params = jax.jit(self.model.init)(jax.random.fold_in(key, 0))
        self.model.params = self.params
        self.tokens = jax.random.randint(jax.random.fold_in(key, 1), (config["tokens"],), 0,
                                         config["vocab_size"], jnp.int32)
        self.out = None

    def solve(self):
        out = self.model(self.tokens)
        with jax.profiler.TraceAnnotation("bench.readback"):
            self.model.readback(out)  # the two log-likelihoods on the host: ends the solve
        self.out = out

    def release(self):
        out = self.out
        # the two scalars that were read back are not compared: a sum of 128 log-probabilities
        # is off by 3e-4 .. 2.3e-3 in the program and by 1.2e-3 .. 5.3e-3 in the float8 control
        # (errors of both signs cancel in it), so no limit tells the two apart (PERF.md)
        self.got = {"logits": out.logits, "mtp_logits": out.mtp_logits,
                    "routes": list(out.chosen)}
        self.out = self.model = None

    def compare(self, precision: str) -> dict:
        import reference_xing4

        from reference import rms_gap

        def forward(p):
            return reference_xing4.forward(self.params, self.tokens, self.cfg,
                                     self.cfg["continuation"], p)

        ref = forward("float32")
        got = self.got if precision == "float32" else forward(precision)

        differ = rows = 0
        for mine, theirs in zip(got["routes"], ref["routes"]):
            theirs = np.sort(np.asarray(theirs), axis=1)
            mine = np.sort(np.asarray(mine)[:theirs.shape[0]], axis=1)  # MTP: T-1 positions
            differ += int((mine != theirs).any(axis=1).sum())
            rows += theirs.shape[0]
        return {
            "logits_rms_gap": rms_gap(got["logits"], ref["logits"]),
            "mtp_logits_rms_gap": rms_gap(got["mtp_logits"], ref["mtp_logits"]),
            "route_mismatch_share": differ / rows,
        }
