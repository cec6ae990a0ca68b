"""Serving steps: prefill (builds the cache, returns first sampled token) and
decode (one token for the whole batch against the cache).  Greedy argmax
sampling keeps the dry-run deterministic; the engine layer adds temperature.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.model_zoo import Model
from repro.parallel.sharding import (DECODE_RULES, PREFILL_RULES,
                                     use_sharding)


def _sample(model: Model, params, hidden_last, with_logits: bool):
    """Greedy token from the last hidden state, plus its logits when the
    caller checks them against a reference."""
    logits = model.logits(params, hidden_last)       # [B,1,V]
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (tok, logits) if with_logits else (tok,)


def make_prefill_step(model: Model, max_len: int, src_len: Optional[int] = None,
                      mesh=None, rules_table=PREFILL_RULES,
                      with_logits: bool = False):
    """``prefill_step(params, batch) -> (tok, cache[, logits])``."""
    def prefill_step(params, batch):
        with use_sharding(mesh, rules_table):
            leaf = batch.get("tokens", batch.get("tgt_tokens",
                                                 batch.get("embeds")))
            B = leaf.shape[0]
            cache = model.init_cache(B, max_len, src_len=src_len) \
                if model.cfg.family == "encdec" else \
                model.init_cache(B, max_len)
            hidden, cache, _ = model.forward(params, batch, cache=cache)
            tok, *logits = _sample(model, params, hidden[:, -1:], with_logits)
            return (tok, cache, *logits)
    return prefill_step


def make_decode_step(model: Model, mesh=None, rules_table=DECODE_RULES,
                     with_logits: bool = False):
    """``decode_step(params, tokens, cache) -> (tok, cache[, logits])``."""
    def decode_step(params, tokens, cache):
        with use_sharding(mesh, rules_table):
            hidden, cache, _ = model.forward(params, {"tokens": tokens},
                                             cache=cache, decode=True)
            tok, *logits = _sample(model, params, hidden, with_logits)
            return (tok, cache, *logits)
    return decode_step
