"""The comparisons that decide ``correct``.  Each is a number and a limit,
and passes where the number is at most its limit."""
from __future__ import annotations

import sys
import time

import numpy as np


def logits_rel_err(served, ref) -> float:
    """||served - ref|| / ||ref|| over every compared logit."""
    s = np.asarray(served, np.float64)
    r = np.asarray(ref, np.float64)
    return float(np.linalg.norm(s - r) / np.linalg.norm(r))


def token_gaps(ref, tok):
    """Per compared position: how far the reference's logit of the token
    put first lies below the reference's best, in standard deviations of
    the reference's logits at that position.  ``ref`` [n, V], ``tok`` [n]."""
    r = np.asarray(ref, np.float64)
    t = np.asarray(tok, np.int64)
    picked = np.take_along_axis(r, t[:, None], 1)[:, 0]
    return (r.max(1) - picked) / r.std(1)


def cache_rel_err(errs) -> float:
    """Worst layer's ||served - ref|| / ||ref|| of the K or V cache, from the
    reference's per-layer sums (dk, rk, dv, rv) [4, L]."""
    e = np.asarray(errs, np.float64)
    return float(max(np.sqrt(e[0] / e[1]).max(), np.sqrt(e[2] / e[3]).max()))


def serving_readings(ref, m: dict, weights, served: dict,
                     rows_per_block: int, control: bool):
    """The served logits, tokens and (where the step returns it) K/V cache
    of ``served`` against the plain float32 reference ``ref`` on the same
    weights: {number: reading}.  With ``control``, also the control's
    readings on the same positions: the reference with every matmul operand
    in float8 put in the program's place (else None)."""
    fwd = ref.make_forward(m)
    t0 = time.perf_counter()
    ref_lg, cache_errs, _ = ref.logits_in_blocks(
        fwd, weights, served["tokens"], served["pos_idx"], rows_per_block,
        served["served_k"], served["served_v"])
    V = m["vocab_size"]
    ref_flat = ref_lg.reshape(-1, V)
    print(f"[check] reference over {served['tokens'].shape} tokens: "
          f"{time.perf_counter() - t0} s", file=sys.stderr, flush=True)
    read = {
        "logits_rel_err": logits_rel_err(served["logits"].reshape(-1, V),
                                         ref_flat),
        "token_gap_sd": float(token_gaps(
            ref_flat, served["tok"].reshape(-1)).max()),
    }
    if served["served_k"] is not None:
        read["cache_rel_err"] = cache_rel_err(cache_errs)
    if not control:
        return read, None
    cfwd = ref.make_forward(m, quant="fp8", emit_kv=True)
    c_lg, _, c_kv = ref.logits_in_blocks(
        cfwd, weights, served["tokens"], served["pos_idx"], rows_per_block)
    c_flat = c_lg.reshape(-1, V)
    c_read = {"logits_rel_err": logits_rel_err(c_flat, ref_flat),
              "token_gap_sd": float(token_gaps(
                  ref_flat, c_flat.argmax(1)).max())}
    if served["served_k"] is not None:
        _, c_errs, _ = ref.logits_in_blocks(
            fwd, weights, served["tokens"], served["pos_idx"],
            rows_per_block, c_kv[0], c_kv[1])
        c_read["cache_rel_err"] = cache_rel_err(c_errs)
    return read, c_read


def replay_checks(em, prof, rep) -> dict:
    """The replay consumed the profile's own totals, and what the atoms
    burned is that amount quantized: within half an iteration per schedule
    row (copied from the repository's chip smoke test)."""
    want = prof.totals
    sched = em.compile(prof)
    desc = sched.describe()
    rows = desc["n_rows"]
    fpi, bpi = em.compute.flops_per_iter(), em.memory.bytes_per_iter()
    out = {
        "consumed_flops_rel": (abs(rep.consumed.flops - want.flops)
                               / want.flops, 1e-9),
        "consumed_bytes_rel": (abs(rep.consumed.hbm_bytes - want.hbm_bytes)
                               / want.hbm_bytes, 1e-9),
        "burned_flops_iters": (abs(desc["compute_iters"] * fpi - want.flops)
                               / fpi, 0.5 * rows),
        "burned_bytes_iters": (abs(desc["memory_iters"] * bpi
                                   - want.hbm_bytes) / bpi, 0.5 * rows),
    }
    if want.ici_total > 0:
        wire_rows = sum(r.ici_total > 0 for s in sched.segments
                        for r in s.rows)
        per = sched.collective_quant.wire_bytes_per_iter
        out["wire_bytes_iters"] = (abs(rep.emulated_ici_bytes
                                       - want.ici_total) / per,
                                   0.5 * max(wire_rows, 1))
    return out


def against(readings: dict, limits: dict) -> dict:
    """{number: (reading, limit)} for every reading that has a limit."""
    return {k: (v, limits[k]) for k, v in readings.items() if k in limits}


def verdict(checks: dict) -> bool:
    return all(np.isfinite(v) and v <= lim for v, lim in checks.values())
