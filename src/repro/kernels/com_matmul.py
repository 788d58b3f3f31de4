"""Pallas TPU kernel: tiled matmul with fused ROFM epilogue.

Domino's PE (CIM crossbar MAC) + ROFM inter-memory functions (Tab. II)
adapted to the MXU: the K-loop accumulates partial sums in a VMEM f32
scratch (the analogue of partial sums riding the ROFM plane — never spilled
to HBM), and the epilogue (Add=bias, Act=relu/silu/gelu, Bp=residual) is
applied on the LAST K step before the single HBM writeback — computing on
the move instead of a separate elementwise pass over HBM. The residual is
added after the activation (``act(acc + bias) + residual``), or, with
``residual_first``, before it: ``act(acc + bias + residual)``, a residual
network's join.

:func:`com_matmul` takes its blocks as given (128 by default).
:func:`com_matmul_padded` takes each block it is not given from
:func:`choose_blocks`, which reads the matmul's ``(M, K, N)`` and dtype:
among block shapes that Mosaic accepts (a multiple of the tile, 8 rows or
128 lanes for 4-byte types, or the whole dimension) and whose
double-buffered working set fits the VMEM budget, it takes those that
divide their dimensions, so that nothing is padded, then the fewest grid
steps, then the fewest bytes re-read from HBM.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _epilogue(acc, bias, activation, residual, residual_first=False):
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if residual is not None and residual_first:
        acc = acc + residual.astype(jnp.float32)
        residual = None
    if activation == "relu":
        acc = jax.nn.relu(acc)
    elif activation == "silu":
        acc = jax.nn.silu(acc)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    return acc


def _kernel(x_ref, w_ref, *rest, activation, nk, has_bias, has_residual,
            residual_first):
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
            residual_first,
        )
        o_ref[...] = acc.astype(o_ref.dtype)


def com_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    residual: Optional[jnp.ndarray] = None,
    residual_first: bool = False,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jnp.ndarray:
    """x: (M, K), w: (K, N) -> (M, N) with fused epilogue; ``residual``
    (M, N) is added after the activation, or before it where
    ``residual_first``. ``name`` names the kernel, and so its op in the
    compiled HLO and a device trace."""
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
        residual_first=residual_first,
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


#: what :func:`vmem_bytes` may come to: Mosaic's default scoped VMEM limit
#: on a TPU v5e is 16 MiB, a kernel that asks for more fails to compile,
#: and the rest is margin
VMEM_BUDGET_BYTES = 12 * 2**20
LANES = 128


def _sublanes(itemsize: int) -> int:
    """Rows of one VMEM tile: 8 for 4-byte types, 16 for 2-byte ones."""
    return 8 * max(1, 4 // itemsize)


def vmem_bytes(bm: int, bk: int, bn: int, itemsize: int,
               residual: bool = False) -> int:
    """Scoped VMEM of a :func:`com_matmul` on blocks ``(bm, bk) @ (bk,
    bn)``, each rounded up to whole tiles as Mosaic lays them out: x, w and
    the output double-buffered, with a ``residual`` its ``(bm, bn)`` block
    double-buffered too, the float32 accumulator, and the scratch
    Mosaic adds for the product. That scratch was read off the v5e
    compiler (the least ``vmem_limit_bytes`` that compiles, float32
    operands at ``HIGHEST``): up to four float32 ``(bm, 128)`` stripes,
    and four times the x block where the output is wider than one lane
    tile.
    """
    sub = _sublanes(itemsize)

    def tiles(rows, cols, size, rows_tile):
        return _round_up(rows, rows_tile) * _round_up(cols, LANES) * size

    x = tiles(bm, bk, itemsize, sub)
    out = tiles(bm, bn, itemsize, sub)
    buffers = 2 * (x + tiles(bk, bn, itemsize, sub) + out
                   + (out if residual else 0)) + tiles(bm, bn, 4, 8)
    scratch = 4 * tiles(bm, LANES, 4, 8)
    if _round_up(bn, LANES) > LANES:
        scratch += 4 * x
    return buffers + scratch


def _divisors(n: int) -> List[int]:
    small = [d for d in range(1, int(n ** 0.5) + 1) if n % d == 0]
    return sorted(set(small + [n // d for d in small]))


def _block_candidates(dim: int, tile: int, given: Optional[int]) -> List[int]:
    """The blocks one dimension may take: ``given`` alone where the caller
    gave it; else the whole dimension and every multiple of ``tile`` that
    divides it, and, where ``dim`` is no multiple of ``tile``, the
    multiples of ``tile`` that divide it rounded up to one (these pad)."""
    if given is not None:
        return [given]
    out = {dim} | {b for b in _divisors(dim) if b % tile == 0}
    if dim % tile:
        padded = _round_up(dim, tile)
        out |= {b for b in _divisors(padded) if b % tile == 0}
    return sorted(out)


@functools.lru_cache(maxsize=1024)
def choose_blocks(M: int, K: int, N: int, itemsize: int, *,
                  block_m: Optional[int] = None,
                  block_n: Optional[int] = None,
                  block_k: Optional[int] = None,
                  residual: bool = False) -> Tuple[int, int, int]:
    """``(bm, bn, bk)`` for an ``(M, K) @ (K, N)`` :func:`com_matmul` of
    ``itemsize``-byte operands, with an ``(M, N)`` residual where
    ``residual``; a block given is kept, and the others are chosen around
    it.

    Each block is the whole dimension or a multiple of its tile (rows of
    x and the output: 8 for 4-byte types; K and N: 128 lanes). Among
    them the rule takes, in this order: a working set
    (:func:`vmem_bytes`) within ``VMEM_BUDGET_BYTES``; blocks that divide
    their dimensions, so that :func:`com_matmul_padded` pads nothing;
    the fewest grid steps; the fewest bytes read from HBM, x being read
    ``N / bn`` times and w ``M / bm`` times.
    """
    sub = _sublanes(itemsize)
    best, best_key = None, None
    for bm in _block_candidates(M, sub, block_m):
        for bn in _block_candidates(N, LANES, block_n):
            for bk in _block_candidates(K, LANES, block_k):
                Mp, Np, Kp = (_round_up(M, bm), _round_up(N, bn),
                              _round_up(K, bk))
                steps = (Mp // bm) * (Np // bn) * (Kp // bk)
                reads = Mp * Kp * (Np // bn) + Kp * Np * (Mp // bm)
                key = (max(0, vmem_bytes(bm, bk, bn, itemsize, residual)
                           - VMEM_BUDGET_BYTES),
                       (Mp, Np, Kp) != (M, N, K), steps, reads, -bm)
                if best_key is None or key < best_key:
                    best, best_key = (bm, bn, bk), key
    return best


class MatmulPlan(NamedTuple):
    """One :func:`com_matmul_padded` call as it runs: its shape, blocks,
    grid steps, the bytes of the padded copies it makes, and, where it
    joins a residual, the bytes of that ``(M, N)`` shortcut it reads."""

    m: int
    k: int
    n: int
    bm: int
    bn: int
    bk: int
    steps: int
    pad_bytes: int
    residual_bytes: int = 0
    joins: bool = False


def matmul_plan(M: int, K: int, N: int, itemsize: int, *,
                block_m: Optional[int] = None, block_n: Optional[int] = None,
                block_k: Optional[int] = None,
                residual: bool = False) -> MatmulPlan:
    """How :func:`com_matmul_padded` runs ``(M, K) @ (K, N)``, joining an
    ``(M, N)`` residual where ``residual``, with the blocks it is given
    and :func:`choose_blocks` picks the rest."""
    bm, bn, bk = choose_blocks(M, K, N, itemsize, block_m=block_m,
                               block_n=block_n, block_k=block_k,
                               residual=residual)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)
    pad = ((Mp * Kp if (Mp, Kp) != (M, K) else 0)
           + (Kp * Np if (Kp, Np) != (K, N) else 0)
           + (Mp * Np if residual and (Mp, Np) != (M, N) else 0))
    return MatmulPlan(M, K, N, bm, bn, bk,
                      (Mp // bm) * (Np // bn) * (Kp // bk), pad * itemsize,
                      M * N * itemsize if residual else 0, residual)


def com_matmul_padded(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    residual: Optional[jnp.ndarray] = None,
    residual_first: bool = False,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    name: Optional[str] = None,
) -> jnp.ndarray:
    """:func:`com_matmul` for arbitrary (unaligned) shapes.

    Blocks not given come from :func:`choose_blocks` (see
    :func:`matmul_plan`). Where a block does not divide its dimension,
    zero-pads that dimension up to the next block multiple, runs the
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
    plan = matmul_plan(M, K, N, x.dtype.itemsize, block_m=block_m,
                       block_n=block_n, block_k=block_k,
                       residual=residual is not None)
    Mp, Kp, Np = (_round_up(M, plan.bm), _round_up(K, plan.bk),
                  _round_up(N, plan.bn))
    # the block padding and the slice back are scoped apart from the
    # kernel, so that a device trace can tell them from it
    with jax.named_scope("pad"):
        xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K))) if (Mp, Kp) != (M, K) else x
        wp = jnp.pad(w, ((0, Kp - K), (0, Np - N))) if (Kp, Np) != (K, N) else w
        bp = None
        if bias is not None:
            assert bias.shape == (N,)
            bp = jnp.pad(bias, (0, Np - N)) if Np != N else bias
        rp = None
        if residual is not None:
            assert residual.shape == (M, N), (residual.shape, (M, N))
            rp = (jnp.pad(residual, ((0, Mp - M), (0, Np - N)))
                  if (Mp, Np) != (M, N) else residual)
    out = com_matmul(
        xp, wp, bias=bp, activation=activation, residual=rp,
        residual_first=residual_first,
        block_m=plan.bm, block_n=plan.bn, block_k=plan.bk,
        interpret=interpret, name=name,
    )
    if (Mp, Np) == (M, N):
        return out
    with jax.named_scope("unpad"):
        return out[:M, :N]
