"""Decode traffic.  A traffic mix whose ``step`` is ``decode``
(``bench/traffic/<name>.json``) has ``batch`` prompts of ``prompt`` tokens
prefilled in set-up, ``prefill_rows`` at a time, into a ``cache``-position
cache; the window then decodes the whole batch one token a step through
that cache.  After ``cycle`` steps the cache position goes back to the
prompt's end and the first token is fed again, so every step attends to a
cache of the same length and the window's length does not change what is
timed.  ``prompt + cycle`` never passes ``cache``.  Every seed gives the
same sizes; only the tokens differ.
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
        B, S, T, K = t["batch"], t["prompt"], t["cache"], t["cycle"]
        assert S + K <= T, (S, K, T)
        engine = Engine(model, params, batch_slots=B, max_len=T, mesh=mesh,
                        keep_logits=True)
        self.prompts = rng.integers(0, vocab, (B, S), dtype=np.int32)
        chunk = t["prefill_rows"]
        toks, caches = [], []
        for i in range(0, B, chunk):
            tok, cache, _ = engine.prefill(
                params, {"tokens": jnp.asarray(self.prompts[i:i + chunk])})
            toks.append(tok)
            caches.append(cache)
        tok0 = jnp.concatenate(toks, 0)
        cache = jax.tree.map(lambda *xs: jnp.concatenate(xs, 1), *caches)
        del toks, caches
        self.exe = engine.decode.lower(params, tok0, cache).compile()
        shard = self.exe.input_shardings[0]
        self.tok0 = jax.device_put(tok0, shard[1])
        self.pos0 = np.asarray(cache["attn"]["pos"])
        self.pos_sharding = shard[2]["attn"]["pos"]
        self.cache = jax.device_put(cache, shard[2])
        del cache
        self.tok = self.tok0
        self.outs = [None] * K
        self.j = 0
        #: the shapes the step's analytic operations are counted from
        self.work = {"batch": B, "q_len": 1, "kv_len": T}

    def step(self):
        if self.j == self.t["cycle"]:
            self.cache = {"attn": {**self.cache["attn"],
                                   "pos": jax.device_put(
                                       self.pos0, self.pos_sharding)}}
            self.tok, self.j = self.tok0, 0
        tok, self.cache, logits = self.exe(self.params, self.tok,
                                           self.cache)
        self.outs[self.j] = (tok, logits)
        self.tok = tok
        self.j += 1
        return tok, logits

    def served(self, rng):
        """Every position of the last cycle (as far as it ran) for
        ``check_rows`` rows drawn from the seed."""
        n = sum(o is not None for o in self.outs)
        B, S = self.t["batch"], self.t["prompt"]
        rows = np.sort(rng.choice(B, min(self.t["check_rows"], B),
                                  replace=False))
        fed = [np.asarray(self.tok0)[rows, 0]] + \
            [np.asarray(self.outs[j][0])[rows, 0] for j in range(n - 1)]
        tok = np.stack([np.asarray(self.outs[j][0])[rows, 0]
                        for j in range(n)], 1)
        lg = np.stack([np.asarray(self.outs[j][1][rows, 0], np.float32)
                       for j in range(n)], 1)
        seqs = np.concatenate([self.prompts[rows], np.stack(fed, 1)], 1)
        return {"tokens": seqs.astype(np.int32),
                "pos_idx": np.arange(S, S + n, dtype=np.int32),
                "logits": lg, "tok": tok, "served_k": None, "served_v": None}

    def readings(self, ref, m: dict, rng, control: bool):
        """The compared numbers, and with ``control`` the control's on the
        same positions.  Frees the cache before the reference runs."""
        served = self.served(rng)
        self.cache = None
        self.outs = [None] * len(self.outs)
        return checks.serving_readings(ref, m, self.params, served,
                                       self.t["ref_rows_per_block"], control)
