"""Kernel-layer microbenchmarks: the OCS client-norm reduction and the
attention hot-spot.  The Pallas kernels run compiled on a TPU and in
interpret mode on any other backend (``kernels/ops.py`` decides), where
their wall-clock is a correctness proxy, not a device number.  Every line
names the device it ran on; FLOP counts are derived analytically."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import csv_line
from repro.core import ocs
from repro.kernels import ops
from repro.models.layers import chunked_attention


def _time(fn, *args, reps=10):
    fn(*args)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    dev = jax.devices()
    where = f"device={dev[0].platform}:{dev[0].device_kind}:x{len(dev)}"
    # interpret mode off a TPU: those timings are a correctness proxy
    mode = "tpu" if dev[0].platform == "tpu" else "interp"
    line = lambda name, us, derived: csv_line(name, us, f"{derived} {where}")
    key = jax.random.PRNGKey(0)
    # client norms over a 32-client x 4M-param update matrix
    upd = jax.random.normal(key, (32, 1 << 22), jnp.float32)
    w = jnp.full((32,), 1 / 32)
    t_jnp = _time(jax.jit(lambda u: ocs.client_norms({"u": u}, w)), upd, reps=5)
    line("client_norms_xla_32x4M", t_jnp, f"bytes={upd.size*4}")
    t_int = _time(
        lambda u: ops.client_sqnorms(u[:, : 1 << 14], chunk=4096), upd, reps=2,
    )
    line(f"client_sqnorms_pallas_{mode}_32x16K", t_int, f"bytes={32 << 16}")
    # fused masked scale-&-aggregate (OCS Eq. 2 contraction)
    scale = jnp.where(jnp.arange(32) % 4 == 0, 32 / 6.0, 0.0)
    t_agg = _time(
        lambda u: ops.masked_scale_aggregate(u[:, : 1 << 14], scale, chunk=4096),
        upd, reps=2,
    )
    line(f"masked_scale_aggregate_pallas_{mode}_32x16K", t_agg, f"bytes={32 << 16}")
    t_agg_xla = _time(
        jax.jit(lambda u: jnp.sum(u * scale[:, None], axis=0)), upd, reps=5
    )
    line("masked_scale_aggregate_xla_32x4M", t_agg_xla, f"bytes={upd.size*4}")

    # attention: dense vs chunked (flash-style) at 4k, f32
    b, s, h, hd = 1, 4096, 8, 128
    q, k, v = [
        jax.random.normal(jax.random.fold_in(key, i), (b, s, h, hd)) for i in range(3)
    ]
    flops = 4.0 * b * h * s * s * hd  # qk + pv
    t_chunk = _time(
        jax.jit(lambda a, b_, c: chunked_attention(a, b_, c, window=None)), q, k, v,
        reps=3,
    )
    line("attention_chunked_4k", t_chunk, f"gflops={flops/1e9:.1f}")
    t_win = _time(
        jax.jit(lambda a, b_, c: chunked_attention(a, b_, c, window=1024)), q, k, v,
        reps=3,
    )
    line("attention_chunked_4k_swa1024", t_win, f"gflops={flops/1e9:.1f}")


if __name__ == "__main__":
    run()
