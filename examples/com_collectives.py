"""COM-on-TPU example: Domino's partial-sum-on-the-move as a JAX collective.

    PYTHONPATH=src python examples/com_collectives.py

Runs in a subprocess pinned to the CPU with 8 forced host devices
(forced host devices exist only there): compares the GSPMD
all-reduce baseline against the COM ring (reduce-scatter built from
ppermute with per-hop accumulation + fused ROFM epilogue), verifying both
numerics and the 2x ICI-byte reduction from the compiled HLO.
"""
import os
import subprocess
import sys
import textwrap

CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.com import make_com_matmul
    from repro.parallel.collectives import matmul_strategy
    from repro.launch.hlo_analysis import analyze_hlo
    from jax.sharding import AxisType

    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 512), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (512, 256), jnp.float32)

    com_mm = make_com_matmul(mesh, "model")
    y = com_mm(x, w, epilogue="silu")      # Act fused on the last hop
    ref = jax.nn.silu(x @ w)
    print("numerics: max err", float(jnp.max(jnp.abs(y - ref))))

    for strat in ("psum", "com"):
        mm = matmul_strategy(mesh, strat)
        txt = jax.jit(mm).lower(x, w).compile().as_text()
        r = analyze_hlo(txt, num_devices=8)
        print(f"{strat:5s}: ICI bytes/dev = {r['collective_bytes_total']:,.0f} "
              f"kinds={list(r['collective_bytes_per_device'])}")
""")

proc = subprocess.run([sys.executable, "-c", CHILD], text=True,
                      cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))) or ".")
sys.exit(proc.returncode)
