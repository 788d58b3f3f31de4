"""Multi-device assertions, run as a SUBPROCESS with its own XLA_FLAGS
(the brief forbids forcing host device count globally in conftest).

Usage: python tests/_mesh_checks.py  -> exit 0 iff all checks pass.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# forced host devices exist only on the CPU platform; pinned, this child
# never tries to take an accelerator that its parent may hold
os.environ["JAX_PLATFORMS"] = "cpu"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.com import (
    com_all_gather,
    com_matmul_local,
    com_matmul_local_bidir,
    com_reduce_scatter,
    make_com_matmul,
)
from repro.parallel.collectives import matmul_strategy, wire_bytes
from repro.train.grad_compress import compressed_pod_psum


def check_com_collectives():
    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    key = jax.random.PRNGKey(0)

    # reduce-scatter == sum of parts
    xg = jax.random.normal(key, (64, 16, 5))
    f = jax.shard_map(lambda xp: com_reduce_scatter(xp, "model"), mesh=mesh,
                      in_specs=P("model"), out_specs=P("model"), check_vma=False)
    out = f(xg)
    ref = xg.reshape(8, 8, 16, 5).sum(0).reshape(128, 5)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    # com matmul + epilogue == dense
    x = jax.random.normal(key, (4, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
    com_mm = make_com_matmul(mesh, "model")
    np.testing.assert_allclose(com_mm(x, w), x @ w, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        com_mm(x, w, epilogue="silu"), jax.nn.silu(x @ w), rtol=1e-4, atol=1e-4
    )

    # bidirectional ring
    fb = jax.shard_map(lambda xl, wl: com_matmul_local_bidir(xl, wl, "model"),
                       mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
                       out_specs=P(None, "model"), check_vma=False)
    np.testing.assert_allclose(fb(x, w), x @ w, rtol=1e-4, atol=1e-4)

    # all-gather
    xa = jax.random.normal(key, (16, 3))
    fg = jax.shard_map(lambda xl: com_all_gather(xl, "model").reshape(-1, xl.shape[-1]),
                       mesh=mesh, in_specs=P("model", None), out_specs=P(None, None),
                       check_vma=False)
    np.testing.assert_allclose(fg(xa), xa, rtol=0, atol=0)

    # strategy selector: psum vs com agree
    for strat in ("psum", "com", "com_bidir"):
        mm = matmul_strategy(mesh, strat)
        np.testing.assert_allclose(mm(x, w), x @ w, rtol=1e-4, atol=1e-4)
    # COM halves the wire bytes vs all-reduce
    assert wire_bytes("com", 1024, 8) == 0.5 * wire_bytes("psum", 1024, 8)
    print("com collectives ok")


def check_com_collective_bytes_in_hlo():
    """COM lowers to collective-permute only (no all-reduce)."""
    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    x = jnp.ones((4, 64))
    w = jnp.ones((64, 32))
    com_mm = make_com_matmul(mesh, "model")
    txt = jax.jit(com_mm).lower(x, w).compile().as_text()
    assert "collective-permute" in txt
    assert "all-reduce(" not in txt.replace("all-reduce-start", "")
    mm_psum = matmul_strategy(mesh, "psum")
    txt2 = jax.jit(mm_psum).lower(x, w).compile().as_text()
    assert "all-reduce" in txt2
    print("hlo collective structure ok")


def check_grad_compress():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    key = jax.random.PRNGKey(0)
    grads = {"a": jax.random.normal(key, (16, 8)), "b": jax.random.normal(key, (4,))}
    reduced, err = compressed_pod_psum(grads, None, mesh, axis="pod")
    # grads replicated across pod -> mean == identity (up to int8 quant)
    for k in grads:
        np.testing.assert_allclose(reduced[k], grads[k], rtol=0.03, atol=0.03)
        assert err[k].shape == grads[k].shape
    # error feedback: residual equals quantization error
    assert float(jnp.max(jnp.abs(err["a"]))) < float(jnp.max(jnp.abs(grads["a"]))) * 0.02
    print("grad compress ok")


def check_sharded_train_step():
    """One real sharded train step on a 2x4 mesh (reduced smollm)."""
    from repro.configs import get_config
    from repro.models.transformer import CallConfig, build_model
    from repro.parallel import sharding as sh
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import make_train_step

    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("smollm-135m").reduced()
    arules = sh.act_rules(mesh, job="train")
    cc = CallConfig(dp_size=2, remat="block", shard_fn=sh.make_shard_fn(mesh, arules))
    model = build_model(cfg, cc)
    ocfg = OptConfig(lr=1e-3, total_steps=10)
    params = model.init(jax.random.PRNGKey(0))
    prules = sh.param_rules(mesh)
    pshard = prules.tree_shardings(model.axes_tree(), params)
    params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, pshard)
    state = {"params": params, "opt": init_opt_state(params, ocfg), "rng": jax.random.PRNGKey(0)}
    step = jax.jit(make_train_step(model, ocfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks}
    with mesh:
        state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # compare against single-device result
    model1 = build_model(cfg, CallConfig(dp_size=2, remat="block"))
    params1 = model1.init(jax.random.PRNGKey(0))
    state1 = {"params": params1, "opt": init_opt_state(params1, ocfg), "rng": jax.random.PRNGKey(0)}
    _, metrics1 = make_train_step(model1, ocfg)(state1, batch)
    np.testing.assert_allclose(float(metrics["loss"]), float(metrics1["loss"]), rtol=2e-2)
    print("sharded train step ok:", float(metrics["loss"]))


def check_elastic_remesh_restore():
    """Save on a 2x4 mesh, restore resharded onto 1x4 (simulated node loss)."""
    import tempfile

    from repro.checkpoint import checkpoint as ck
    from repro.runtime.elastic import MeshPlan, build_mesh, plan_remesh

    mesh_a = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    tree = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh_a, P("data", "model"))), tree
    )
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, 7, jax.tree.map(np.asarray, tree))
        new_plan = plan_remesh(MeshPlan(data=2, model=4), available_devices=4)
        assert new_plan is not None and new_plan.devices == 4
        assert new_plan.accum_multiplier == 2  # global batch preserved
        mesh_b = build_mesh(new_plan)
        shardings = {"w": NamedSharding(mesh_b, P("data", "model"))}
        restored, man = ck.restore(d, tree, shardings=shardings)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(64).reshape(8, 8))
        assert man["step"] == 7
    print("elastic remesh restore ok")


if __name__ == "__main__":
    check_com_collectives()
    check_com_collective_bytes_in_hlo()
    check_grad_compress()
    check_sharded_train_step()
    check_elastic_remesh_restore()
    print("ALL MESH CHECKS PASSED")
