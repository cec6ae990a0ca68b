"""The program's side of a Qwen2 configuration: the model the program serves
from the benchmark's configuration file, and the operations of one of its
steps, counted from the published shapes alone (the benchmark compares the
profiler's count with this one).

Every architecture has a file like this one, ``bench/archs/<arch>.py``,
named by the configuration's ``arch``; the plain reference of the same name
lives in ``bench/reference/``.
"""
from __future__ import annotations

import dataclasses


def model_config(c: dict):
    """The program's registered configuration with every size set from the
    benchmark's configuration file, which is what runs."""
    from repro.configs import get_config
    m = c["config"]
    base = get_config(c["registry"])
    return dataclasses.replace(
        base, num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], norm_eps=m["rms_norm_eps"],
        attn=dataclasses.replace(base.attn, rope_theta=m["rope_theta"]))


def step_flops(m: dict, work: dict) -> float:
    """2 x multiply-adds of one serving step over ``work["batch"]``
    sequences of ``work["q_len"]`` new tokens each, attending to
    ``work["kv_len"]`` positions: every layer matmul once per query token,
    the LM head once per sequence (the step samples from the last position
    only), and QK^T plus PV over the whole ``kv_len`` (masked entries
    included, as the program computes them).  ``m`` holds the
    configuration's published keys (``hidden_size``, ...)."""
    batch, q_len, kv_len = work["batch"], work["q_len"], work["kv_len"]
    d, L = m["hidden_size"], m["num_hidden_layers"]
    f, V = m["intermediate_size"], m["vocab_size"]
    hq, hk = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // hq
    layer = d * hq * hd + 2 * d * hk * hd + hq * hd * d + 3 * d * f
    dense = 2.0 * L * layer * batch * q_len
    head = 2.0 * d * V * batch
    attn = 4.0 * L * batch * hq * q_len * kv_len * hd
    return dense + head + attn
