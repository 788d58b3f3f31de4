"""Smoke check on a TPU: the executor, the serve engine and the sweep kernel.

    python chip_smoke.py               # one chip: the three device paths
    python chip_smoke.py --four-chips  # four chips: the sharded paths only

Everything runs in this one process; it starts no other. The script first
checks that JAX's first device is a TPU and exits non-zero, before any
other work, when it is not. Each phase then checks its own result and
raises on a failed check, so the script exits non-zero and never prints
the last line. Numbers go to standard output, one ``name: value`` per
line; the last line is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases on one chip:

* executor: ``vgg16-imagenet`` at full ImageNet widths, batch 8, through
  ``ProgramExecutor(backend="jax", interpret=False)``; 16 Pallas kernels
  in the compiled chain, event totals equal to the closed forms, logits
  of the first images against the float64 NumPy oracle;
* serve: ``smollm-135m`` at its published widths through ``Engine``,
  4 greedy requests; the engine's batch-4 decode logits, fed the tokens
  of ``generate_sequential``, against its batch-1 ones, and each token
  where the two outputs part explained by those logits;
* sweep: the >=1e5-scenario perf grid on the ``jax`` backend against the
  ``numpy`` oracle.

With ``--four-chips``: the sharded executor (``shard="auto"``, 4 shards)
against the unsharded run, bitwise, and the ``jax-sharded`` sweep against
the ``jax`` sweep, bitwise, each with a check that every device held its
share of the work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

KERNEL_CALL = 'custom_call_target="tpu_custom_call"'

# jax.monitoring events of a lowering and of a backend compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

# Max relative error (max |jax - oracle| / max |oracle|) of the executor's
# float32 chain against the float64 NumPy oracle. The CPU tests hold the
# same chain to 2e-5 (tests/test_executor.py).
EXEC_RTOL = 2e-5

# The serve engine decodes its slots as one batch; generate_sequential
# decodes each request alone. The model computes in bfloat16, and XLA on a
# TPU tiles a batch-4 and a batch-1 matmul differently, so the two traces
# round differently, and greedy decoding picks different tokens where the
# top two logits tie to within that. Fed the same tokens, the engine's
# batch-4 decode logits for smollm-135m on a v5e are at most 0.0432 off
# the batch-1 ones, 2.34 * eps * max|logit| (bfloat16 eps = 2**-7); the
# tolerance is 4 * eps * max|logit| (0.0737 there). Two slots swapped at
# prefill move them by 2.38 to 3.09, 32 to 42 times the tolerance.
SERVE_LOGIT_TOL = 4 * 2.0**-7


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def require_tpu():
    """JAX's first device, or exit non-zero when it is not a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX's first device is {dev.platform!r}, "
                 "not a TPU; nothing was run")
    return dev


def peak_bytes(dev):
    """``peak_bytes_in_use`` of ``dev``, or None where the backend does
    not report memory (the CPU)."""
    stats = dev.memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def _vgg_setup(network: str, batch: int):
    from repro.core.executor import random_weights
    from repro.core.program import compile_program
    from repro.sweep.registry import resolve_network

    program = compile_program(resolve_network(network))
    weights = random_weights(program, seed=0)
    first = program.workload.layers[0]
    rng = np.random.default_rng(0)
    images = rng.normal(size=(batch, first.h_in, first.w_in, first.c_in))
    return program, weights, images


def phase_executor(network: str = "vgg16-imagenet", batch: int = 8,
                   n_check: int = 2, interpret: bool = False,
                   repeats: int = 5) -> None:
    from repro.core.executor import ProgramExecutor
    from repro.core.simulator import network_event_totals

    program, weights, images = _vgg_setup(network, batch)
    layers = program.workload.layers
    ex = ProgramExecutor(program, weights, backend="jax", interpret=interpret)

    # the executor's own jitted chain, compiled ahead of its first run:
    # ex.run then dispatches this executable and compiles nothing more.
    # Lowering also puts the float32 weights on the device.
    t0 = time.perf_counter()
    lowered = ex.lower(images)
    say("executor.lower_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    say("executor.compile_s", time.perf_counter() - t0)
    n_kernels = compiled.as_text().count(KERNEL_CALL)
    say("executor.tpu_custom_calls", n_kernels)
    check(n_kernels == (0 if interpret else len(layers)),
          f"{n_kernels} Pallas kernels in the compiled chain, expected "
          f"one per layer ({len(layers)})")
    compiles = []

    def on_event(name, secs, **kwargs):
        if name in COMPILE_EVENTS:
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        # each run ends with the logits copied to the host
        runs = [ex.run(images) for _ in range(repeats + 1)]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    check(not compiles, f"ex.run compiled again after ex.lower: {compiles}")
    say("executor.batch", batch)
    say("executor.steady_run_s", [r.wall_s for r in runs[1:]])
    say("executor.peak_bytes_in_use", peak_bytes(jax.devices()[0]))

    res = runs[0]
    check(all(np.array_equal(r.outputs, res.outputs) for r in runs),
          "repeated runs of one batch gave different logits")
    check(res.outputs.shape == (batch, layers[-1].c_out),
          f"logits shape {res.outputs.shape}")
    check(bool(np.isfinite(res.outputs).all()), "non-finite logits")
    totals = network_event_totals(layers, program.arch)
    events_match = dict(res.events) == dict(totals)
    say("executor.events_match", events_match)
    check(events_match, "executor event totals != network_event_totals")

    t0 = time.perf_counter()
    ref = ProgramExecutor(program, weights, backend="numpy").run(
        images[:n_check]).outputs
    say("executor.oracle_s", time.perf_counter() - t0)
    got = res.outputs[:n_check]
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    say("executor.max_rel_err_vs_f64", err)
    say("executor.rtol", EXEC_RTOL)
    check(err <= EXEC_RTOL,
          f"executor logits off the float64 oracle by {err} > {EXEC_RTOL}")


def _forced_logits(eng, prompts: np.ndarray, tokens: np.ndarray, *,
                   batched: bool) -> np.ndarray:
    """Float32 logits ``(R, T, V)``, row ``[r, t]`` predicting
    ``tokens[r, t]`` from ``prompts[r]`` and ``tokens[r, :t]``, fed through
    the engine's own jitted prefill and decode step: each request alone on
    a batch-1 cache, as ``generate_sequential`` runs, or (``batched``) all
    requests as one decode batch on the engine's slot pool, as
    ``generate`` runs."""
    import jax.numpy as jnp

    n_req, n_tok = tokens.shape
    vocab = eng.model.cfg.vocab_size
    out = np.empty((n_req, n_tok, vocab), np.float32)

    def keep(logits):
        return np.asarray(logits[:, -1, :vocab], np.float32)

    caches = []
    for r, prompt in enumerate(prompts):
        template = (eng.slots.template if batched
                    else eng.model.init_cache(1, eng.max_seq))
        logits, one = eng._prefill(eng.params, jnp.asarray(prompt[None]),
                                   template)
        out[r, 0] = keep(logits)[0]
        if batched:
            eng.slots.write_prefill(r, one)
        else:
            caches.append(one)
    for t in range(1, n_tok):
        pos = prompts.shape[1] + t - 1
        if batched:
            logits, eng.slots.cache = eng._decode(
                eng.params, jnp.asarray(tokens[:, t - 1:t]), eng.slots.cache,
                jnp.full((n_req,), pos, jnp.int32))
            out[:, t] = keep(logits)
            continue
        for r in range(n_req):
            logits, caches[r] = eng._decode(
                eng.params, jnp.asarray(tokens[r:r + 1, t - 1:t]), caches[r],
                jnp.int32(pos))
            out[r, t] = keep(logits)[0]
    return out


def phase_serve(cfg=None, n_requests: int = 4, prompt_len: int = 32,
                max_new: int = 16, max_seq: int = 128) -> None:
    from repro.configs import get_config
    from repro.models.transformer import CallConfig, build_model
    from repro.serve.engine import Engine, Request

    cfg = cfg or get_config("smollm-135m")
    say("serve.arch", f"{cfg.name} d_model={cfg.d_model} "
                      f"layers={cfg.num_layers} vocab={cfg.vocab_size}")
    model = build_model(cfg, CallConfig(remat="none", dp_size=1))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    def wave():
        return [Request(prompt=p.copy(), max_new_tokens=max_new)
                for p in prompts]

    eng = Engine(model, params, batch=n_requests, max_seq=max_seq)
    t0 = time.perf_counter()
    eng.generate(wave(), seed=0)          # compiles prefill and decode step
    say("serve.first_wave_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    out = eng.generate(wave(), seed=0)    # tokens are host ints: synced
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in out)
    check(n_tok == n_requests * max_new, f"{n_tok} tokens generated")
    say("serve.wave_s", dt)
    say("serve.tokens_per_s", n_tok / dt)

    # The sequential trace's tokens, fed back through the engine one
    # request at a time and as one batch on its slot pool. Replaying
    # generate_sequential reproduces its tokens; the batched replay
    # reproduces generate's tokens up to and including each request's
    # first divergence (the inputs agree until then). Its logits stay
    # within tol of the sequential ones, so a divergence can only be a
    # near-tie that the rounding flipped.
    ref = eng.generate_sequential(wave(), seed=0)
    prompts = np.stack(prompts)
    tokens = np.array([r.out_tokens for r in ref], np.int32)
    alone = _forced_logits(eng, prompts, tokens, batched=False)
    check(np.array_equal(alone.argmax(-1), tokens),
          "replaying generate_sequential gave other tokens")
    batched = _forced_logits(eng, prompts, tokens, batched=True)
    gap = np.abs(batched - alone).max(-1)           # (R, T)
    tol = SERVE_LOGIT_TOL * float(np.abs(alone).max())
    say("serve.decode_logits_max_abs_diff_batched_vs_alone", float(gap.max()))
    say("serve.logits_tol", tol)
    check(gap.max() <= tol,
          f"batched decode logits differ by {gap.max()} > {tol}")

    identical = 0
    for i, req in enumerate(out):
        got = np.array(req.out_tokens)
        diff = np.flatnonzero(got != tokens[i])
        k = int(diff[0]) if diff.size else len(got) - 1
        check(np.array_equal(batched[i, :k + 1].argmax(-1), got[:k + 1]),
              f"request {i}: the batched replay does not give generate's "
              f"tokens up to position {k}")
        if not diff.size:
            identical += 1
            continue
        margin = float(alone[i, k, tokens[i, k]] - alone[i, k, got[k]])
        say(f"serve.request{i}.first_diff",
            f"position {k}: batched {got[k]} vs sequential {tokens[i, k]}, "
            f"logit margin {margin}, batched-vs-alone gap {gap[i, k]}")
    say("serve.requests_token_identical", identical)


def phase_sweep(grid=None) -> None:
    from benchmarks.sweep import JAX_RTOL, check_backends_agree, perf_grid
    from repro.sweep import run_sweep

    grid = grid or perf_grid()
    say("sweep.scenarios", grid.n_scenarios)
    ref = run_sweep(grid, backend="numpy")
    run_sweep(grid, backend="jax")                # compile
    got = run_sweep(grid, backend="jax")
    say("sweep.numpy_engine_s", ref.engine_wall_s)
    say("sweep.jax_engine_s", got.engine_wall_s)
    err = check_backends_agree(ref, got, rtol=JAX_RTOL)   # raises past it
    say("sweep.max_rel_err_vs_numpy", err)
    say("sweep.rtol", JAX_RTOL)


def phase_four_chips(network: str = "vgg16-imagenet", batch: int = 8,
                     interpret: bool = False, grid=None) -> None:
    """The sharded executor and sweep next to their one-device twins."""
    from benchmarks.sweep import JAX_RTOL, check_backends_agree, perf_grid
    from repro.core.executor import ProgramExecutor
    from repro.sweep import COLUMNS, run_sweep

    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} devices, expected 4")

    # sweep first, while device memory is empty: each device's peak then
    # shows the scenario shard it evaluated
    grid = grid or perf_grid()
    sharded = run_sweep(grid, backend="jax-sharded")
    peaks = [peak_bytes(d) for d in devices]
    say("four.sweep.peak_bytes_in_use", peaks)
    share = grid.n_scenarios // 4 * 8 * len(COLUMNS)
    check(all(p is None or p >= share for p in peaks),
          f"a device holds less than its {share}-byte share of the "
          "sweep's output columns")
    one = run_sweep(grid, backend="jax", chunk_size=grid.n_scenarios)
    same = all(np.array_equal(sharded.columns[c], one.columns[c])
               for c in COLUMNS)
    say("four.sweep.bitwise_equal_jax", same)
    check(same, "jax-sharded sweep differs from the jax sweep")
    say("four.sweep.max_rel_err_vs_numpy", check_backends_agree(
        run_sweep(grid, backend="numpy"), sharded, rtol=JAX_RTOL))

    program, weights, images = _vgg_setup(network, batch)
    ex4 = ProgramExecutor(program, weights, backend="jax",
                          interpret=interpret, shard="auto")
    say("four.executor.n_shards", ex4.n_shards)
    check(ex4.n_shards == 4, f"sharded executor uses {ex4.n_shards} shards")
    out4 = ex4.run(images).outputs
    peaks = [peak_bytes(d) for d in devices]
    say("four.executor.peak_bytes_in_use", peaks)
    w_bytes = sum(w.size * 4 for w in ex4.weights)   # float32 on every shard
    check(all(p is None or p >= w_bytes for p in peaks),
          f"a device never held the {w_bytes}-byte float32 weights")
    ex1 = ProgramExecutor(program, weights, backend="jax", interpret=interpret)
    out1 = ex1.run(images).outputs
    same = bool(np.array_equal(out4, out1))
    say("four.executor.bitwise_equal_one_device", same)
    check(same, "sharded logits differ from the one-device run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on four chips")
    args = ap.parse_args(argv)

    dev = require_tpu()
    from repro.launch.compile_cache import init_compile_cache

    say("compile_cache", init_compile_cache())
    say("device", f"{dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if args.four_chips:
        phase_four_chips()
    else:
        for name, phase in (("executor", phase_executor),
                            ("serve", phase_serve), ("sweep", phase_sweep)):
            t0 = time.perf_counter()
            phase()
            say(f"{name}.phase_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
