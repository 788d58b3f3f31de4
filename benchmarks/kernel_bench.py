"""Kernel micro-benchmarks: Pallas (interpret) correctness-checked against
ref + wall-time of the jnp reference path (CPU wall time is NOT the TPU
number — the TPU-side performance statement lives in the roofline analysis;
this harness exists so the same benches run unchanged on a real TPU).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.com_matmul import com_matmul
from repro.kernels.conv2d_com import conv2d_com
from repro.kernels.flash_attention import flash_attention


def _time(fn, *args, reps=5):
    fn(*args)  # compile/warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def rows():
    out = []
    key = jax.random.PRNGKey(0)

    x = jax.random.normal(key, (512, 512), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (512, 512), jnp.float32)
    jfn = jax.jit(lambda x, w: ref.com_matmul_ref(x, w, activation="silu"))
    us = _time(jfn, x, w)
    y_k = com_matmul(x, w, activation="silu", interpret=True)
    err = float(jnp.max(jnp.abs(y_k - jfn(x, w))))
    out.append(("com_matmul_512", us, f"maxerr={err:.1e} flops={2*512**3:.2e}"))

    q = jax.random.normal(key, (4, 512, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 2), (4, 512, 64), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 3), (4, 512, 64), jnp.float32)
    jfn = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True))
    us = _time(jfn, q, k, v)
    y_k = flash_attention(q, k, v, causal=True, interpret=True)
    err = float(jnp.max(jnp.abs(y_k - jfn(q, k, v))))
    out.append(("flash_attn_b4s512", us, f"maxerr={err:.1e}"))

    xc = jax.random.normal(key, (32, 32, 64), jnp.float32)
    wc = jax.random.normal(jax.random.fold_in(key, 4), (3, 3, 64, 64), jnp.float32)
    jfn = jax.jit(lambda x, w: ref.conv2d_com_ref(x, w, activation="relu"))
    us = _time(jfn, xc, wc)
    y_k = conv2d_com(xc, wc, activation="relu", interpret=True)
    err = float(jnp.max(jnp.abs(y_k - jfn(xc, wc))))
    out.append(("conv2d_com_32x32x64", us, f"maxerr={err:.1e} (no im2col)"))
    return out


def main():
    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
