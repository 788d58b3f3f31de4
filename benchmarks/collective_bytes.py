"""COM ring vs GSPMD all-reduce: collective bytes counted in compiled HLO.

For the same row-parallel matmul, Domino's COM reduce-scatter should move
half the bytes of the baseline all-reduce and lower to neighbour
collective-permutes only (no global reduction op). This script compiles
both for 8 forced host devices on the CPU, in a child process pinned to
the CPU, and counts the collective bytes in the HLO. It is a byte count
from the compiler, not a measurement on a TPU.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_CHILD = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.parallel.collectives import matmul_strategy, wire_bytes
    from repro.launch.hlo_analysis import analyze_hlo
    from jax.sharding import AxisType

    mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
    M, K, N = 256, 4096, 2048
    x = jnp.ones((M, K), jnp.bfloat16)
    w = jnp.ones((K, N), jnp.bfloat16)
    out = {}
    for strat in ("psum", "com", "com_bidir"):
        mm = matmul_strategy(mesh, strat)
        txt = jax.jit(mm).lower(x, w).compile().as_text()
        res = analyze_hlo(txt, num_devices=8)
        out[strat] = {
            "coll_bytes_per_dev": res["collective_bytes_total"],
            "by_kind": res["collective_bytes_per_device"],
            "analytic_wire_bytes": wire_bytes(strat, M * N * 2, 8),
        }
    print(json.dumps(out))
    """
)


def run():
    proc = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                          text=True, timeout=300, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    out = run()
    print(f"{'strategy':10s} {'HLO coll bytes/dev':>20s} {'analytic':>12s}  kinds")
    for k, v in out.items():
        print(f"{k:10s} {v['coll_bytes_per_dev']:20,.0f} {v['analytic_wire_bytes']:12,.0f}  "
              f"{list(v['by_kind'])}")
    ratio = out["psum"]["coll_bytes_per_dev"] / max(out["com"]["coll_bytes_per_dev"], 1)
    print(f"\nCOM moves {ratio:.2f}x fewer ICI bytes than all-reduce "
          f"(paper's data-movement reduction, TPU form)")
    return out


if __name__ == "__main__":
    main()
