"""Prefill traffic.  A traffic mix whose ``step`` is ``prefill``
(``bench/traffic/<name>.json``) gives ``pool`` batches of ``batch`` prompts
of ``prompt`` tokens, drawn from the seed, prefilled in turn into a cache
of ``cache`` positions.  Each call serves one token per prompt and fills
the cache.  Every seed gives the same sizes; only the tokens differ.

Each kind of step has a file like this one, ``bench/steps/<step>.py``,
with a ``Step`` that builds the compiled step the window drives, calls it,
and compares what it served with the reference once the window has closed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import checks


class Step:
    def __init__(self, t: dict, model, params, mesh, vocab: int, rng):
        from repro.serve.engine import Engine
        self.t, self.params = t, params
        P, B, S = t["pool"], t["batch"], t["prompt"]
        assert S <= t["cache"]
        engine = Engine(model, params, batch_slots=B, max_len=t["cache"],
                        mesh=mesh, keep_logits=True)
        self.prompts = rng.integers(0, vocab, (P, B, S), dtype=np.int32)
        example = {"tokens": jnp.asarray(self.prompts[0])}
        self.exe = engine.prefill.lower(params, example).compile()
        shard = self.exe.input_shardings[0][1]
        self.batches = [jax.device_put({"tokens": p}, shard)
                        for p in self.prompts]
        self.outs = [None] * P
        self.i = 0
        #: the shapes the step's analytic operations are counted from
        self.work = {"batch": B, "q_len": S, "kv_len": S}

    def step(self):
        """One call of the compiled prefill; returns what to block on."""
        p = self.i % len(self.batches)
        out = self.exe(self.params, self.batches[p])
        self.outs[p] = out
        self.i += 1
        return out

    def served(self, rng):
        """The served results of the last call on every pool batch (or of
        those that ran), for ``check_rows`` rows drawn from the seed."""
        done = [p for p, o in enumerate(self.outs) if o is not None]
        B, S = self.t["batch"], self.t["prompt"]
        rows = [(p, r) for p in done for r in range(B)]
        n = min(self.t["check_rows"], len(rows))
        pick = sorted(rng.choice(len(rows), n, replace=False))
        rows = [rows[i] for i in pick]
        tok = np.stack([np.asarray(self.outs[p][0])[r, 0] for p, r in rows])
        lg = np.stack([np.asarray(self.outs[p][2][r, 0], np.float32)
                       for p, r in rows])
        ks = jnp.stack([self.outs[p][1]["attn"]["k"][:, r, :S]
                        for p, r in rows], 1)
        vs = jnp.stack([self.outs[p][1]["attn"]["v"][:, r, :S]
                        for p, r in rows], 1)
        return {"tokens": np.stack([self.prompts[p, r] for p, r in rows]),
                "pos_idx": np.array([S - 1], np.int32),
                "logits": lg[:, None], "tok": tok[:, None],
                "served_k": ks, "served_v": vs}

    def readings(self, ref, m: dict, rng, control: bool):
        """The compared numbers, and with ``control`` the control's on the
        same positions.  Frees every served cache but those it compares."""
        served = self.served(rng)
        self.outs = [None] * len(self.outs)
        self.batches = None
        return checks.serving_readings(ref, m, self.params, served,
                                       self.t["ref_rows_per_block"], control)
