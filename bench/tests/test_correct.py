"""The correctness check, at a size a CPU holds: the program passes it, the
float8 control fails it, and so does a run whose served step is broken
underneath, once for each fault a serving cell can have.

Each test drives ``run_cell`` (the whole run but the look for a chip) on a
two-layer Qwen2 with the published layout at small widths.  The limits are
this size's: a 28-layer stack at full width amplifies rounding far more,
and its limits are set from its own readings on the chip (``PERF.md``).
"""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import run as R

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 977

TRAFFIC = {
    "prefill": dict(step="prefill", batch=2, prompt=16, cache=16, pool=2,
                    check_rows=4, ref_rows_per_block=2, replays_per_step=1),
    "decode": dict(step="decode", batch=4, prompt=16, cache=32,
                   prefill_rows=2, cycle=4, check_rows=4, replays_per_step=2,
                   ref_rows_per_block=2),
}
#: served bf16 at this size reads ~0.01 (logits, cache) and gaps under 0.1
#: sd; the float8 control reads ~0.15 (chip-free CPU runs)
LIMITS = {"logits_rel_err": 0.05, "token_gap_sd": 1.0, "cache_rel_err": 0.05,
          "profile_flops_rel": 1.0}


def tiny_cell(step):
    cfg = R.load_json(R.BENCH, "configs", "qwen2-1.5b.json")
    cfg["config"].update(hidden_size=64, intermediate_size=128,
                         num_attention_heads=4, num_key_value_heads=2,
                         num_hidden_layers=2, vocab_size=256)
    return SimpleNamespace(name=f"qwen2-1.5b.{step}", chips=1, config=cfg,
                           traffic=TRAFFIC[step], limits=LIMITS)


def run_tiny(step, control=False):
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    return R.run_cell(tiny_cell(step), SEED, 0.2, False, bench=bench,
                      require_chip=False, control=control)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_program_passes_and_control_fails(step):
    r = run_tiny(step, control=True)
    assert r["correct"], r["checks"]
    assert r["control"]["correct"] is False, r["control"]
    c = r["control"]["readings"]
    assert c["logits_rel_err"] > LIMITS["logits_rel_err"], c
    assert c["logits_rel_err"] > 3 * r["checks"]["logits_rel_err"]["value"]


# -- faults planted in the served step ---------------------------------------

def _state_unchanged(step_fn, kind):
    """The step hands back the state it was given (decode: the cache it
    read; prefill: an empty cache)."""
    if kind == "decode":
        def f(params, tok, cache):
            t, _, lg = step_fn(params, tok, cache)
            return t, cache, lg
    else:
        def f(params, batch):
            t, cache, lg = step_fn(params, batch)
            return t, jax.tree.map(jnp.zeros_like, cache), lg
    return f


def _half_batch(step_fn, kind):
    """Only the first half of the batch is computed; the rest repeats it."""
    def dup(x, axis):
        h = x.shape[axis] // 2
        first = jax.lax.slice_in_dim(x, 0, h, axis=axis)
        return jnp.concatenate([first, first], axis)

    def f(params, *args):
        t, cache, lg = step_fn(params, *args)
        cache = {"attn": {k: dup(v, 1) for k, v in cache["attn"].items()}}
        return dup(t, 0), cache, dup(lg, 0)
    return f


def _token_altered(step_fn, kind):
    """The first row's served token is another than the step computed."""
    def f(params, *args):
        t, cache, lg = step_fn(params, *args)
        return t.at[0, 0].set((t[0, 0] + 1) % lg.shape[-1]), cache, lg
    return f


def _no_exchange(step_fn, kind):
    """Tensor-parallel layers without their exchange: each row-parallel
    matmul (attention out, MLP down) keeps the first chip's partial sum
    alone, as a step whose all-reduce was left out would serve it."""
    def f(params, *args):
        lay = params["layers"]
        a, m = lay["attn"], lay["mlp"]
        hq, ff = a["wo"].shape[1], m["wo"].shape[1]
        keep_h = (jnp.arange(hq) < hq // 4)[None, :, None, None]
        keep_f = (jnp.arange(ff) < ff // 4)[None, :, None]
        lay = {**lay,
               "attn": {**a, "wo": a["wo"] * keep_h.astype(a["wo"].dtype)},
               "mlp": {**m, "wo": m["wo"] * keep_f.astype(m["wo"].dtype)}}
        return step_fn({**params, "layers": lay}, *args)
    return f


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_broken_step_is_not_correct(monkeypatch, step, fault):
    from repro.serve import engine as engine_mod
    maker = "make_prefill_step" if step == "prefill" else "make_decode_step"
    real = getattr(engine_mod, maker)

    def broken(*a, **k):
        return FAULTS[fault](real(*a, **k), step)
    monkeypatch.setattr(engine_mod, maker, broken)
    r = run_tiny(step)
    assert not r["correct"], r["checks"]


def tp4_cell():
    cfg = R.load_json(R.BENCH, "configs", "qwen2-7b.json")
    cfg["config"].update(hidden_size=128, intermediate_size=256,
                         num_attention_heads=8, num_key_value_heads=4,
                         num_hidden_layers=2, vocab_size=512)
    return SimpleNamespace(name="qwen2-7b.prefill.tp4", chips=4, config=cfg,
                           traffic=TRAFFIC["prefill"], limits=LIMITS)


def _tp4_main(fault):
    """Run in a process that sees four CPU devices: the tiny
    tensor-parallel cell, its step broken by ``fault`` (or not)."""
    from repro.serve import engine as engine_mod
    if fault != "none":
        real = engine_mod.make_prefill_step
        engine_mod.make_prefill_step = \
            lambda *a, **k: _no_exchange(real(*a, **k), "prefill")
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    r = R.run_cell(tp4_cell(), SEED, 0.2, False, bench=bench,
                   require_chip=False)
    print(json.dumps({"correct": r["correct"], "checks": r["checks"]}))


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_tensor_parallel_exchange(fault):
    """On four devices the sharded step passes, and fails without the
    exchange between its chips."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-m", "bench.tests.test_correct",
                        fault], cwd=R.ROOT, capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] == (fault == "none"), r["checks"]


def test_no_chip_no_result():
    """Held to the CPU, a run exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(R.BENCH, "run.py"),
                        "--workload", "qwen2-1.5b.prefill", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_cell_and_metric_has_its_files():
    """Cells, configurations, traffic, limits and metric readers are found
    by name: what BENCHMARK.json names has a file of its own."""
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = R.cell_spec(w["name"], bench)
        assert cell.config["name"] == w["config"]
        for d in ("archs", "reference"):
            assert os.path.exists(os.path.join(
                R.BENCH, d, cell.config["arch"] + ".py"))
        assert os.path.exists(os.path.join(
            R.BENCH, "steps", cell.traffic["step"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = R.load_module(os.path.join(R.BENCH, "metrics",
                                         m["name"] + ".py"))
        assert callable(mod.read)
    for c in bench["configs"]:
        assert R.load_json(R.ROOT, c["file"])["name"] == c["name"]
    json.dumps(bench)


NEW_METRIC = '''
def read(run):
    return float(run.steps)
'''


def test_a_new_cell_is_files_only(tmp_path):
    """A configuration, a traffic mix, a cell with its limits and a metric
    added to a copy of the benchmark as new files and entries run with no
    existing file edited."""
    import shutil
    root = tmp_path / "checkout"
    shutil.copytree(R.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(os.path.join(R.ROOT, "src"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = tiny_cell("decode").config
    cfg["name"] = "qwen2-tiny"
    (root / "bench/configs/qwen2-tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/decode-small.json").write_text(
        json.dumps(TRAFFIC["decode"]))
    (root / "bench/limits/qwen2-tiny.decode-small.json").write_text(
        json.dumps(LIMITS))
    (root / "bench/metrics/window_steps.py").write_text(NEW_METRIC)
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    bench["configs"].append({"name": "qwen2-tiny", "source": "test",
                             "file": "bench/configs/qwen2-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "qwen2-tiny.decode-small",
                               "config": "qwen2-tiny",
                               "traffic": "decode-small", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "window_steps", "unit": "steps",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import json, sys; from bench import run as R; "
            "b = R.load_json(R.ROOT, 'BENCHMARK.json'); "
            "c = R.cell_spec('qwen2-tiny.decode-small', b); "
            "r = R.run_cell(c, 5, 0.2, False, bench=b, require_chip=False); "
            "print(json.dumps(r))")
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["metrics"]["window_steps"]["value"] >= 1
    assert all(p.read_bytes() == b for p, b in before.items())


if __name__ == "__main__":
    _tp4_main(sys.argv[1])
