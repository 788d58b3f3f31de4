"""Computing-On-the-Move collectives in JAX (DESIGN.md §2).

Domino's key mechanism — partial sums accumulated hop-by-hop between tiles
instead of shipped to a global buffer — maps onto the TPU ICI as a ring
reduce-scatter built from ``lax.ppermute``: at every step each device adds
its local partial block to the arriving accumulator and forwards it to the
neighbour. Compared to the GSPMD baseline (all-reduce after a row-sharded
matmul) this:

  * moves (n-1)/n of the bytes instead of 2(n-1)/n  (2x less ICI traffic),
  * exposes per-hop overlap: the partial block for hop t+1 is computed
    while hop t's accumulator is in flight (compute-on-the-move),
  * lands the result *distributed* (output-stationary in the last tile),
    which composes with sequence/tensor-parallel consumers, and
  * fuses the ROFM epilogue (Add/Act/Bp — bias, activation, residual) into
    the final hop.

All functions are meant to run inside ``shard_map`` over the reduction mesh
axis. ``com_matmul`` is the drop-in replacement for a row-parallel matmul
(x feature-sharded, w row-sharded) used by the hillclimb configurations.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring_perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# COM ring reduce-scatter (inside shard_map)
# ---------------------------------------------------------------------------


def com_reduce_scatter(x_parts: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Ring reduce-scatter with on-the-move accumulation.

    x_parts: (n, chunk, ...) — this device's partial contribution for each of
    the n destination shards (n = lax.psum(1, axis_name)).
    Returns this device's fully-reduced chunk: (chunk, ...).

    Hop t: accumulator for destination d = (me - t - 1) mod n arrives; we add
    our local partial for that destination and forward. After n-1 hops the
    accumulator for ``me`` has visited everyone — Domino's partial-sum chain.
    """
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    if n == 1:
        return x_parts[0]

    def body(t, acc):
        # send our running accumulator to the ring successor; the arriving
        # one (from the predecessor) is for chunk (me - t - 2) mod n — add
        # our local partial for that chunk and keep it moving.
        acc = jax.lax.ppermute(acc, axis_name, _ring_perm(n))
        dest = (me - t - 2) % n
        acc = acc + jax.lax.dynamic_index_in_dim(x_parts, dest, keepdims=False)
        return acc

    # init with our partial for chunk (me-1): after n-1 hops every chunk has
    # visited all devices and chunk ``me`` comes to rest here.
    acc0 = jax.lax.dynamic_index_in_dim(x_parts, (me - 1) % n, keepdims=False)
    acc = jax.lax.fori_loop(0, n - 1, body, acc0)
    return acc


def com_all_gather(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Ring all-gather via ppermute (IFM streaming plane / RIFM analogue)."""
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    if n == 1:
        return x[None]

    def body(t, state):
        buf, cur = state
        cur = jax.lax.ppermute(cur, axis_name, _ring_perm(n))
        src = (me - t - 1) % n
        buf = jax.lax.dynamic_update_index_in_dim(buf, cur, src, 0)
        return buf, cur

    buf = jnp.zeros((n,) + x.shape, x.dtype)
    buf = jax.lax.dynamic_update_index_in_dim(buf, x, me, 0)
    buf, _ = jax.lax.fori_loop(0, n - 1, body, (buf, x))
    return buf


# ---------------------------------------------------------------------------
# COM matmul: row-parallel matmul with ring accumulation + fused epilogue
# ---------------------------------------------------------------------------


def com_matmul_local(
    x_local: jnp.ndarray,
    w_local: jnp.ndarray,
    axis_name: str,
    *,
    bias_local: Optional[jnp.ndarray] = None,
    epilogue: Optional[str] = None,       # None | "relu" | "silu" | "gelu"
    residual_local: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Inside shard_map: x_local (..., K/n), w_local (K/n, N) -> (..., N/n).

    The output's N dim lands sharded over ``axis_name`` (output-stationary).
    Per ring hop, the partial block for the chunk about to be forwarded is
    computed just-in-time — XLA overlaps the (independent) next-hop matmul
    with the in-flight ppermute, Domino's compute-on-the-move.

    Epilogue (ROFM inter-memory functions, Tab. II): bias add (Add),
    activation (Act), residual shortcut (Bp) — applied on the final hop only.
    """
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    N = w_local.shape[-1]
    assert N % n == 0, (N, n)
    chunk = N // n

    def w_chunk(d):
        return jax.lax.dynamic_slice_in_dim(w_local, d * chunk, chunk, axis=-1)

    if n == 1:
        out = x_local @ w_local
    else:
        def body(t, acc):
            acc = jax.lax.ppermute(acc, axis_name, _ring_perm(n))
            dest = (me - t - 2) % n
            # compute this hop's partial block *now* (overlaps next ppermute)
            acc = acc + x_local @ w_chunk(dest)
            return acc

        acc0 = x_local @ w_chunk((me - 1) % n)
        out = jax.lax.fori_loop(0, n - 1, body, acc0)

    if bias_local is not None:
        out = out + bias_local
    if epilogue == "relu":
        out = jax.nn.relu(out)
    elif epilogue == "silu":
        out = jax.nn.silu(out)
    elif epilogue == "gelu":
        out = jax.nn.gelu(out)
    if residual_local is not None:
        out = out + residual_local
    return out


def make_com_matmul(mesh: Mesh, axis: str = "model"):
    """Returns com_mm(x, w, ...) running under shard_map on ``mesh``:

    x: (..., K) sharded (..., axis) on K; w: (K, N) sharded (axis, None);
    out: (..., N) sharded (..., axis) on N.
    """

    def com_mm(x, w, *, bias=None, epilogue=None, residual=None):
        ndim = x.ndim
        x_spec = P(*([None] * (ndim - 1) + [axis]))
        w_spec = P(axis, None)
        out_spec = P(*([None] * (ndim - 1) + [axis]))
        b_spec = P(axis)

        args = (x, w)
        specs = [x_spec, w_spec]
        kw = {}
        if bias is not None:
            kw["bias_local"] = bias
        if residual is not None:
            kw["residual_local"] = residual

        def fn(x_l, w_l, *rest):
            it = iter(rest)
            b_l = next(it) if bias is not None else None
            r_l = next(it) if residual is not None else None
            return com_matmul_local(
                x_l, w_l, axis, bias_local=b_l, epilogue=epilogue, residual_local=r_l
            )

        extra = []
        extra_specs = []
        if bias is not None:
            extra.append(bias)
            extra_specs.append(b_spec)
        if residual is not None:
            extra.append(residual)
            extra_specs.append(out_spec)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=tuple(specs + extra_specs),
            out_specs=out_spec, check_vma=False,
        )(x, w, *extra)

    return com_mm


# ---------------------------------------------------------------------------
# Bidirectional COM ring — halves hop latency (beyond-paper: uses both ICI
# directions simultaneously, like Domino's dual-router planes)
# ---------------------------------------------------------------------------


def com_matmul_local_bidir(x_local, w_local, axis_name):
    """As com_matmul_local but splits each chunk across two counter-rotating
    rings: (n-1)/2 hops on each direction instead of n-1 on one."""
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    N = w_local.shape[-1]
    chunk = N // n
    if n == 1:
        return x_local @ w_local
    half = chunk // 2

    def w_chunk(d, lo, size):
        return jax.lax.dynamic_slice_in_dim(w_local, d * chunk + lo, size, axis=-1)

    def body(t, accs):
        a_fw, a_bw = accs
        a_fw = jax.lax.ppermute(a_fw, axis_name, _ring_perm(n, 1))
        a_bw = jax.lax.ppermute(a_bw, axis_name, _ring_perm(n, -1))
        d_fw = (me - t - 2) % n
        d_bw = (me + t + 2) % n
        a_fw = a_fw + x_local @ w_chunk(d_fw, 0, half)
        a_bw = a_bw + x_local @ w_chunk(d_bw, half, chunk - half)
        return a_fw, a_bw

    a_fw0 = x_local @ w_chunk((me - 1) % n, 0, half)
    a_bw0 = x_local @ w_chunk((me + 1) % n, half, chunk - half)
    a_fw, a_bw = jax.lax.fori_loop(0, n - 1, body, (a_fw0, a_bw0))
    return jnp.concatenate([a_fw, a_bw], axis=-1)
