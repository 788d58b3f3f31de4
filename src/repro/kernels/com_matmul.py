"""Pallas TPU kernel: tiled matmul with fused ROFM epilogue.

Domino's PE (CIM crossbar MAC) + ROFM inter-memory functions (Tab. II)
adapted to the MXU: the K-loop accumulates partial sums in a VMEM f32
scratch (the analogue of partial sums riding the ROFM plane — never spilled
to HBM), and the epilogue (Add=bias, Act=relu/silu/gelu, Bp=residual) is
applied on the LAST K step before the single HBM writeback — computing on
the move instead of a separate elementwise pass over HBM.

Block shapes default to MXU-aligned (128 multiples); VMEM working set =
bm*bk + bk*bn (bf16) + bm*bn (f32 acc) — sized well under 16MB v5e VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _epilogue(acc, bias, activation, residual):
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if activation == "relu":
        acc = jax.nn.relu(acc)
    elif activation == "silu":
        acc = jax.nn.silu(acc)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    return acc


def _kernel(x_ref, w_ref, *rest, activation, nk, has_bias, has_residual):
    # rest = [bias_ref?, residual_ref?, o_ref, acc_ref]
    idx = 0
    bias_ref = rest[idx] if has_bias else None
    idx += int(has_bias)
    res_ref = rest[idx] if has_residual else None
    idx += int(has_residual)
    o_ref, acc_ref = rest[idx], rest[idx + 1]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # float32 operands get a float32 product. At the default precision a
    # TPU v5e multiplies them in one bfloat16 pass: 2e-3 relative error on
    # one 576-deep matmul and 6e-3 over vgg16-imagenet's chain, where the
    # executor holds its float32 chain to 2e-5 of the float64 oracle.
    precision = (jax.lax.Precision.HIGHEST
                 if x_ref.dtype == jnp.float32 else None)
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(k == nk - 1)
    def _finish():
        acc = acc_ref[...]
        acc = _epilogue(
            acc,
            bias_ref[...] if bias_ref is not None else None,
            activation,
            res_ref[...] if res_ref is not None else None,
        )
        o_ref[...] = acc.astype(o_ref.dtype)


def com_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    residual: Optional[jnp.ndarray] = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jnp.ndarray:
    """x: (M, K), w: (K, N) -> (M, N) with fused epilogue. ``name`` names
    the kernel, and so its op in the compiled HLO and a device trace."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (x.shape, w.shape, (bm, bn, bk))
    nk = K // bk
    grid = (M // bm, N // bn, nk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if bias is not None:
        assert bias.shape == (N,)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias[None, :])
    if residual is not None:
        assert residual.shape == (M, N)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        args.append(residual)

    kernel = functools.partial(
        _kernel, activation=activation, nk=nk,
        has_bias=bias is not None, has_residual=residual is not None,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name=name,
    )(*args)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def com_matmul_padded(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jnp.ndarray:
    """:func:`com_matmul` for arbitrary (unaligned) shapes.

    Zero-pads every dimension up to the next block multiple, runs the
    kernel, and slices the result back to ``(M, N)``. Zero K-padding adds
    zeros into the VMEM partial-sum accumulation (exact); padded M rows /
    N cols are sliced away before the caller sees them, so the epilogue
    applied to them is irrelevant. This is what lets the whole-program
    executor lower every compiled ``LayerBlock`` einsum — whose shapes
    follow the DNN, not the MXU — onto the one COM kernel.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    Mp, Kp, Np = _round_up(M, block_m), _round_up(K, block_k), _round_up(N, block_n)
    # the block padding and the slice back are scoped apart from the
    # kernel, so that a device trace can tell them from it
    with jax.named_scope("pad"):
        xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K))) if (Mp, Kp) != (M, K) else x
        wp = jnp.pad(w, ((0, Kp - K), (0, Np - N))) if (Kp, Np) != (K, N) else w
        bp = None
        if bias is not None:
            assert bias.shape == (N,)
            bp = jnp.pad(bias, (0, Np - N)) if Np != N else bias
    out = com_matmul(
        xp, wp, bias=bp, activation=activation,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret, name=name,
    )
    if (Mp, Np) == (M, N):
        return out
    with jax.named_scope("unpad"):
        return out[:M, :N]
