"""Host calibration microbenchmarks.

The paper's compute atom is an assembly matmul loop whose throughput defines
"the maximum efficiency Synapse can emulate"; equivalently we measure what
this host actually sustains (matmul FLOP/s, stream bytes/s, file I/O bytes/s)
once, cache it on disk, and atoms use it to convert a resource amount into
loop iterations.  On a TPU the same role is played by the Pallas atoms +
HardwareSpec peaks.
"""
from __future__ import annotations

import json
import os
import re
import tempfile
import time
from dataclasses import asdict, dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def cache_path() -> str:
    """The calibration cache of the default device's kind: a measurement
    taken on one accelerator (or the CPU) never answers for another."""
    kind = re.sub(r"[^\w]+", "_", jax.devices()[0].device_kind).strip("_")
    return os.path.join(tempfile.gettempdir(),
                        f"synapse_calib_{kind or 'unknown'}.json")


@dataclass(frozen=True)
class HostCalibration:
    flops_per_s: float
    stream_bytes_per_s: float
    storage_write_bps: float
    storage_read_bps: float

    def to_json(self):
        return json.dumps(asdict(self))


def _time(fn, min_s=0.2, warmup=1):
    for _ in range(warmup):
        fn()
    n, t0 = 0, time.perf_counter()
    while True:
        fn()
        n += 1
        dt = time.perf_counter() - t0
        if dt > min_s:
            return dt / n


def measure_flops(m: int = 512) -> float:
    a = jnp.ones((m, m), jnp.float32)
    f = jax.jit(lambda x: x @ x)
    f(a).block_until_ready()
    dt = _time(lambda: f(a).block_until_ready())
    return 2.0 * m ** 3 / dt


def measure_stream(nbytes: int = 1 << 26) -> float:
    n = nbytes // 4
    a = jnp.ones((n,), jnp.float32)
    f = jax.jit(lambda x: x * 1.0000001)
    f(a).block_until_ready()
    dt = _time(lambda: f(a).block_until_ready())
    return 2.0 * nbytes / dt              # read + write


def measure_storage(nbytes: int = 1 << 24, block: int = 1 << 20):
    buf = os.urandom(block)
    path = os.path.join(tempfile.gettempdir(), "synapse_cal.bin")

    def wr():
        with open(path, "wb") as f:
            for _ in range(nbytes // block):
                f.write(buf)
            f.flush()
            os.fsync(f.fileno())

    dt_w = _time(wr, min_s=0.3, warmup=0)

    def rd():
        with open(path, "rb") as f:
            while f.read(block):
                pass

    dt_r = _time(rd, min_s=0.1)
    os.unlink(path)
    return nbytes / dt_w, nbytes / dt_r


def calibrate(force: bool = False) -> HostCalibration:
    path = cache_path()
    if not force and os.path.exists(path):
        try:
            with open(path) as f:
                return HostCalibration(**json.load(f))
        except Exception:  # noqa: BLE001
            pass
    flops = measure_flops()
    stream = measure_stream()
    wr, rd = measure_storage()
    cal = HostCalibration(flops_per_s=flops, stream_bytes_per_s=stream,
                          storage_write_bps=wr, storage_read_bps=rd)
    with open(path, "w") as f:
        f.write(cal.to_json())
    return cal
