"""Batched serving engine: continuous prefill+decode over a request queue.

Single-host reference implementation of the serving layer the decode cells
dry-run: fixed-size batch slots, greedy sampling, per-slot stop lengths.
The Synapse runtime watchers can profile ``serve_requests`` exactly like a
training run (examples/serve_profile.py), and the decode-step TTC predicted
by the roofline feeds the SLA/straggler monitor at scale.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model_zoo import Model
from repro.serve.step import make_decode_step, make_prefill_step


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False


class Engine:
    def __init__(self, model: Model, params, *, batch_slots: int = 4,
                 max_len: int = 256, mesh=None, keep_logits: bool = False):
        """``keep_logits``: the steps also return their logits, and
        ``logits`` holds those of the last step served ([B,1,V]), so a
        caller can check the served numbers against a reference."""
        self.model = model
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.keep_logits = keep_logits
        self.logits = None
        self.prefill = jax.jit(make_prefill_step(
            model, max_len, mesh=mesh, with_logits=keep_logits))
        self.decode = jax.jit(make_decode_step(
            model, mesh=mesh, with_logits=keep_logits), donate_argnums=2)

    def serve(self, requests: List[Request]) -> List[Request]:
        """Static batching: pad the wave to batch_slots, prefill, decode to
        the longest max_new_tokens, per-request early stop bookkeeping."""
        for wave_start in range(0, len(requests), self.B):
            wave = requests[wave_start:wave_start + self.B]
            self._serve_wave(wave)
        return requests

    def _serve_wave(self, wave: List[Request]):
        B = self.B
        plen = max(len(r.prompt) for r in wave)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(wave):
            toks[i, plen - len(r.prompt):] = r.prompt   # left-pad
        tok, cache, *logits = self.prefill(self.params,
                                           {"tokens": jnp.asarray(toks)})
        steps = max(r.max_new_tokens for r in wave)
        for i, r in enumerate(wave):
            r.out_tokens.append(int(tok[i, 0]))
        for _ in range(steps - 1):
            tok, cache, *logits = self.decode(self.params, tok, cache)
            t = np.asarray(tok)
            for i, r in enumerate(wave):
                if not r.done and len(r.out_tokens) < r.max_new_tokens:
                    r.out_tokens.append(int(t[i, 0]))
                else:
                    r.done = True
        for r in wave:
            r.done = True
        if logits:
            self.logits = logits[0]
