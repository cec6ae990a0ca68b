"""Plain float32 Qwen2 (arXiv:2407.10671), and the weights both it and the
served model run on.

Nothing here comes from the program under test.  The weights are the
benchmark's own, drawn from the seed in one jitted call in the type they are
served in, in the parameter layout the serving model takes; the check
hands the same weights to this forward pass.  The forward pass has no cache,
no batching tricks and no sharding of its own: layers are scanned and cast to
float32 one at a time, and only the asked-for positions meet the LM head, so
it runs beside the served weights in blocks of rows.

``quant="fp8"`` is the control: the same pass with every matmul operand
rounded to float8 (e4m3, one scale per tensor), the precision below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: the float8 format's largest finite value (e4m3)
FP8_MAX = 448.0


def sizes(m: dict):
    d, hq = m["hidden_size"], m["num_attention_heads"]
    return dict(d=d, L=m["num_hidden_layers"], f=m["intermediate_size"],
                V=m["vocab_size"], hq=hq, hk=m["num_key_value_heads"],
                hd=d // hq, eps=m["rms_norm_eps"], theta=m["rope_theta"],
                tied=bool(m["tie_word_embeddings"]))


def layout(m: dict) -> dict:
    """(shape, standard deviation) of every weight, in the serving model's
    parameter tree: matrices at 1/sqrt(fan-in), so activations keep unit
    scale through every layer; embeddings, biases and norm offsets at 0.02
    (norms scale by 1 + offset)."""
    s = sizes(m)
    d, L, f, V, hq, hk, hd = (s[k] for k in ("d", "L", "f", "V", "hq", "hk",
                                             "hd"))
    small = 0.02
    tree = {
        "embed": ((V, d), small),
        "layers": {
            "ln_attn": {"scale": ((L, d), small)},
            "ln_mlp": {"scale": ((L, d), small)},
            "attn": {"wq": ((L, d, hq, hd), d ** -0.5),
                     "wk": ((L, d, hk, hd), d ** -0.5),
                     "wv": ((L, d, hk, hd), d ** -0.5),
                     "wo": ((L, hq, hd, d), (hq * hd) ** -0.5),
                     "bq": ((L, hq, hd), small),
                     "bk": ((L, hk, hd), small),
                     "bv": ((L, hk, hd), small)},
            "mlp": {"wi_gate": ((L, d, f), d ** -0.5),
                    "wi_up": ((L, d, f), d ** -0.5),
                    "wo": ((L, f, d), f ** -0.5)},
        },
        "ln_final": {"scale": ((d,), small)},
    }
    if not s["tied"]:
        tree["lm_head"] = ((d, V), d ** -0.5)
    return tree


def _leaves(tree, path=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], path + (k,))
    else:
        yield path, tree


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_weights(m: dict, seed_key, dtype=jnp.bfloat16, shardings=None):
    """Every weight of the model from ``seed_key``, in one jitted call on
    the device.  ``shardings`` (a tree like the weights) places each leaf
    where the serving model keeps it, so nothing is resharded later."""
    spec = list(_leaves(layout(m)))

    def gen(key):
        out: dict = {}
        for path, (shape, std) in spec:
            k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
            _set(out, path, (std * jax.random.normal(k, shape, F32))
                 .astype(dtype))
        return out

    return jax.jit(gen, out_shardings=shardings)(seed_key)


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def make_forward(m: dict, quant=None, emit_kv: bool = False):
    """A jitted ``fwd(w, tokens, pos_idx, served_k, served_v)``.

    ``tokens`` [b, S] are whole sequences from position 0.  Returns the
    float32 logits at ``pos_idx`` [P] ([b, P, V]); where the served
    cache is given ([L, b, S, hk, hd] each), the per-layer sums of
    squared difference and of squared reference, for K and for V:
    [4, L] as (dk, rk, dv, rv); and with ``emit_kv`` this pass's own K and
    V in bf16, as a served cache would hold them."""
    s = sizes(m)
    hq, hk, hd, eps, theta = s["hq"], s["hk"], s["hd"], s["eps"], s["theta"]
    G = hq // hk
    q8 = _fp8 if quant == "fp8" else (lambda x: x)
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown precision {quant!r}")

    def mm(eq, a, b):
        return jnp.einsum(eq, q8(a), q8(b))

    def norm(x, scale):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return x * (1.0 + scale.astype(F32))

    def rope(x, pos):                      # x [b, S, h, hd]
        inv = 1.0 / theta ** (jnp.arange(hd // 2, dtype=F32) / (hd // 2))
        ang = pos[:, None].astype(F32) * inv             # [S, hd/2]
        c, sn = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)

    def fwd(w, tokens, pos_idx, served_k, served_v):
        b, S = tokens.shape
        pos = jnp.arange(S)
        causal = pos[None, :] <= pos[:, None]
        x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
        cmp = served_k is not None

        def layer(x, xs):
            p, sk, sv = xs
            p = jax.tree.map(lambda a: a.astype(F32), p)
            a = p["attn"]
            h = norm(x, p["ln_attn"]["scale"])
            q = mm("bsd,dhk->bshk", h, a["wq"]) + a["bq"]
            k = mm("bsd,dhk->bshk", h, a["wk"]) + a["bk"]
            v = mm("bsd,dhk->bshk", h, a["wv"]) + a["bv"]
            q, k = rope(q, pos), rope(k, pos)
            err = jnp.zeros((4,), F32)
            if cmp:
                dk = sk.astype(F32) - k
                dv = sv.astype(F32) - v
                err = jnp.stack([jnp.sum(dk * dk), jnp.sum(k * k),
                                 jnp.sum(dv * dv), jnp.sum(v * v)])
            kk, vv = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
            sc = mm("bqhd,bkhd->bhqk", q, kk) / np.sqrt(hd)
            sc = jnp.where(causal, sc, -jnp.inf)
            o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vv)
            x = x + mm("bshk,hkd->bsd", o, a["wo"])
            ml = p["mlp"]
            h = norm(x, p["ln_mlp"]["scale"])
            u = jax.nn.silu(mm("bsd,df->bsf", h, ml["wi_gate"])) \
                * mm("bsd,df->bsf", h, ml["wi_up"])
            x = x + mm("bsf,fd->bsd", u, ml["wo"])
            kv = (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)) \
                if emit_kv else None
            return x, (err, kv)

        xs = (w["layers"], served_k, served_v)
        x, (errs, kv) = jax.lax.scan(layer, x, xs)
        h = norm(x[:, pos_idx], w["ln_final"]["scale"])          # [b, P, d]
        head = (w["embed"].T if s["tied"] else w["lm_head"]).astype(F32)
        return mm("bpd,dv->bpv", h, head), errs.T, kv

    @jax.jit
    def run(w, tokens, pos_idx, served_k=None, served_v=None):
        with jax.default_matmul_precision("highest"):
            return fwd(w, tokens, pos_idx, served_k, served_v)

    return run


def logits_in_blocks(fwd, w, tokens, pos_idx, rows_per_block: int,
                     served_k=None, served_v=None):
    """``fwd`` over ``tokens`` a block of rows at a time.  Returns the
    logits [b, P, V] on the host as float32, the summed cache errors
    [4, L] (zeros where no served cache is given), and the pass's own K
    and V ([L, b, S, hk, hd] each, where ``fwd`` emits them)."""
    outs, errs, ks, vs = [], 0.0, [], []
    b = tokens.shape[0]
    assert b % rows_per_block == 0, (b, rows_per_block)
    for i in range(0, b, rows_per_block):
        sl = slice(i, i + rows_per_block)
        lg, e, kv = fwd(w, tokens[sl], pos_idx,
                        None if served_k is None else served_k[:, sl],
                        None if served_v is None else served_v[:, sl])
        outs.append(np.asarray(lg, np.float32))
        errs = errs + np.asarray(e, np.float64)
        if kv is not None:
            ks.append(kv[0])
            vs.append(kv[1])
    kv = (jnp.concatenate(ks, 1), jnp.concatenate(vs, 1)) if ks else None
    return np.concatenate(outs), errs, kv
