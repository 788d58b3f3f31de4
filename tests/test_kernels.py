"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with
hypothesis sweeps over shapes/dtypes per the brief."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # stripped container: deterministic fallback
    from _hypothesis_stub import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.com_matmul import com_matmul, com_matmul_padded, matmul_plan
from repro.kernels.conv2d_com import conv2d_com
from repro.kernels.flash_attention import flash_attention, flash_attention_gqa


def rtol_for(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@given(
    m=st.sampled_from([64, 128, 256]),
    n=st.sampled_from([64, 128]),
    k=st.sampled_from([64, 128, 384]),
    bm=st.sampled_from([32, 64, 128]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    act=st.sampled_from([None, "relu", "silu", "gelu"]),
)
@settings(max_examples=12, deadline=None)
def test_com_matmul_sweep(m, n, k, bm, dtype, act):
    key = jax.random.PRNGKey(m * n + k)
    x = jax.random.normal(key, (m, k), dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), dtype)
    b = jax.random.normal(jax.random.fold_in(key, 2), (n,), dtype)
    y = com_matmul(x, w, bias=b, activation=act, block_m=bm, interpret=True)
    yr = ref.com_matmul_ref(x, w, bias=b, activation=act)
    np.testing.assert_allclose(
        y.astype(np.float32), yr.astype(np.float32),
        rtol=rtol_for(dtype), atol=k * (0.05 if dtype == jnp.bfloat16 else 1e-4),
    )


def _pallas_grids(fn, *args):
    """``(grid, x block shape)`` of each ``pallas_call`` that ``fn`` traces."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [(tuple(e.params["grid_mapping"].grid),
             tuple(b.block_size for b in
                   e.params["grid_mapping"].block_mappings[0].block_shape))
            for e in eqns if e.primitive.name == "pallas_call"]


def _relu_dot(x, w):
    return jnp.maximum(
        jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST), 0.0)


@pytest.mark.parametrize("m, k, n, split_k", [
    (1, 4096, 1000, True),     # M = 1 (an FC layer at batch 1), N = 1000
    (196, 4608, 512, True),    # M = 196 whole (vgg16's conv10 at batch 1)
    (3136, 27, 64, False),     # K = 27 whole (conv0), N = 64 whole
    (1024, 576, 64, False),    # K = 576 whole (conv1)
    (256, 4096, 10, True),     # N = 10 whole (vgg11-cifar's logits)
], ids=["m1", "m196", "k27", "k576", "n10"])
def test_com_matmul_padded_chosen_blocks(m, k, n, split_k):
    """The chosen blocks, as the executor runs them, against ``jnp.dot``
    at ``HIGHEST``; K split into blocks that divide it where ``split_k``."""
    plan = matmul_plan(m, k, n, 4)
    assert plan.pad_bytes == 0
    assert (plan.bk < k) == split_k, plan
    key = jax.random.PRNGKey(m + k + n)
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)

    def fn(x, w):
        return com_matmul_padded(x, w, activation="relu", interpret=True)

    assert _pallas_grids(fn, x, w) == [
        ((m // plan.bm, n // plan.bn, k // plan.bk), (plan.bm, plan.bk))]
    y, ref = fn(x, w), _relu_dot(x, w)
    np.testing.assert_allclose(y, ref, rtol=2e-5,
                               atol=1e-5 * float(jnp.abs(ref).max()))


def test_com_matmul_padded_block_m_overrides_the_chooser():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (64, 576), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (576, 64), jnp.float32)
    chosen = matmul_plan(64, 576, 64, 4)
    assert (chosen.bm, chosen.bk, chosen.bn) == (64, 576, 64)
    for block_m, grid in ((8, (8, 1, 1)), (48, (2, 1, 1))):

        def fn(x, w):
            return com_matmul_padded(x, w, activation="relu",
                                     block_m=block_m, interpret=True)

        # block_m is kept; K and N are still chosen; 48 pads M to 96
        assert _pallas_grids(fn, x, w) == [(grid, (block_m, 576))]
        ref = _relu_dot(x, w)
        np.testing.assert_allclose(fn(x, w), ref, rtol=2e-5,
                                   atol=1e-5 * float(jnp.abs(ref).max()))


def test_com_matmul_residual_epilogue():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (128, 128))
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 128))
    r = jax.random.normal(jax.random.fold_in(key, 2), (128, 128))
    y = com_matmul(x, w, residual=r, activation="relu", interpret=True)
    np.testing.assert_allclose(
        y, ref.com_matmul_ref(x, w, residual=r, activation="relu"), rtol=1e-4, atol=1e-4
    )



def _operands(m=128, k=128, n=128):
    key = jax.random.PRNGKey(3)
    return [jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, shape in enumerate(((m, k), (k, n), (m, n)))]


def test_com_matmul_residual_order_is_unchanged_by_default():
    """The existing callers' order, ``act(acc) + residual``, to the bit:
    the default, ``residual_first=False``, and the same sum made outside
    the kernel all agree."""
    x, w, r = _operands()
    y = com_matmul(x, w, residual=r, activation="relu", interpret=True)
    explicit = com_matmul(x, w, residual=r, activation="relu",
                          residual_first=False, interpret=True)
    outside = com_matmul(x, w, activation="relu", interpret=True) + r
    np.testing.assert_array_equal(y, explicit)
    np.testing.assert_array_equal(y, outside)


def test_com_matmul_joins_the_residual_before_the_activation():
    """``residual_first``: ``relu(acc + residual)``, a residual network's
    join, in the kernel's epilogue."""
    x, w, r = _operands()
    y = com_matmul(x, w, residual=r, activation="relu", residual_first=True,
                   interpret=True)
    acc = com_matmul(x, w, interpret=True)
    np.testing.assert_array_equal(y, jax.nn.relu(acc + r))
    assert (y >= 0).all() and not np.array_equal(
        y, com_matmul(x, w, residual=r, activation="relu", interpret=True))


def test_com_matmul_padded_pads_the_residual_as_the_output():
    """A residual of an unaligned ``(M, N)`` rides the same block padding
    as the output, and its bytes are counted in the plan."""
    x, w, r = _operands(100, 64, 200)
    y = com_matmul_padded(x, w, residual=r, activation="relu",
                          residual_first=True, block_m=64, block_n=128,
                          interpret=True)
    want = jax.nn.relu(jnp.dot(x, w, precision="highest") + r)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    plan = matmul_plan(100, 64, 200, 4, block_m=64, block_n=128,
                       residual=True)
    assert plan.joins and plan.residual_bytes == 4 * 100 * 200
    no_res = matmul_plan(100, 64, 200, 4, block_m=64, block_n=128)
    assert plan.pad_bytes - no_res.pad_bytes == 4 * 128 * 256

@given(
    s=st.sampled_from([128, 256]),
    hd=st.sampled_from([64, 128]),
    bq=st.sampled_from([64, 128]),
    bkv=st.sampled_from([64, 128]),
    causal=st.booleans(),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
@settings(max_examples=10, deadline=None)
def test_flash_attention_sweep(s, hd, bq, bkv, causal, dtype):
    key = jax.random.PRNGKey(s + hd)
    q = jax.random.normal(key, (2, s, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, hd), dtype)
    y = flash_attention(q, k, v, causal=causal, block_q=bq, block_kv=bkv, interpret=True)
    yr = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        y.astype(np.float32), yr.astype(np.float32),
        rtol=rtol_for(dtype), atol=0.05 if dtype == jnp.bfloat16 else 1e-5,
    )


def test_flash_gqa_matches_model_oracle():
    from repro.models.attention import naive_attention

    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 128, 8, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 2, 64))
    y = flash_attention_gqa(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(y, naive_attention(q, k, v, causal=True), rtol=1e-4, atol=1e-5)


@given(
    h=st.sampled_from([8, 12, 16]),
    w=st.sampled_from([8, 10]),
    c=st.sampled_from([3, 8, 16]),
    m=st.sampled_from([8, 32]),
    k=st.sampled_from([1, 3, 5]),
    s=st.sampled_from([1, 2]),
    p=st.integers(0, 2),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
@settings(max_examples=14, deadline=None)
def test_conv2d_com_sweep(h, w, c, m, k, s, p, dtype):
    if h + 2 * p < k or w + 2 * p < k:
        return
    key = jax.random.PRNGKey(h * w + c)
    x = jax.random.normal(key, (h, w, c), dtype)
    wt = jax.random.normal(jax.random.fold_in(key, 1), (k, k, c, m), dtype)
    y = conv2d_com(x, wt, stride=s, padding=p, interpret=True)
    yr = ref.conv2d_com_ref(x, wt, stride=s, padding=p)
    np.testing.assert_allclose(
        y.astype(np.float32), yr.astype(np.float32),
        rtol=rtol_for(dtype), atol=0.25 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_ops_wrappers_dispatch():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 64))
    y_i = ops.com_matmul(x, w, backend="interpret")
    y_r = ops.com_matmul(x, w, backend="ref")
    np.testing.assert_allclose(y_i, y_r, rtol=1e-4, atol=1e-4)


# ---------------- fused sLSTM kernel ----------------


@given(
    s=st.sampled_from([32, 64]), d=st.sampled_from([32, 64]),
    h=st.sampled_from([2, 4]), chunk=st.sampled_from([8, 16, 32]),
)
@settings(max_examples=8, deadline=None)
def test_slstm_fused_matches_scan(s, d, h, chunk):
    from repro.kernels.slstm import slstm_fused
    from repro.models import xlstm as xl

    key = jax.random.PRNGKey(s + d)
    B = 2
    x = jax.random.normal(key, (B, s, d), jnp.float32)
    params, _ = xl.init_slstm(key, d, h)
    ref = xl.slstm_forward(params, x, h)
    gx = (jnp.einsum("bsd,dk->bsk", x, params["wg"]) + params["bg"]).reshape(B, s, 4, d)
    hs = slstm_fused(gx, params["rg"], h, chunk=chunk, interpret=True)
    out = jnp.einsum("bsh,hd->bsd", hs, params["wo"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_slstm_traffic_model():
    from repro.kernels.slstm import hbm_traffic_model

    m = hbm_traffic_model(16, 4096, 1024, 4)
    assert m["reduction_x"] > 10  # the kernel's raison d'etre
