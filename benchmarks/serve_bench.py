"""Continuous-batching serve benchmark: the CI serve-throughput artifact.

Drives ``repro.serve.engine.Engine`` over a wave of synthetic requests and
emits machine-readable JSON with the three numbers that define the serve
path's health:

* ``tokens_s``                 — generated tokens per wall-clock second;
* ``decode_steps_per_token``   — jitted decode-step calls per
  decode-generated token, i.e. excluding the per-request prefill-sampled
  token (exactly 1/occupancy; the continuous-batching win: scales with
  max new tokens, **not** with the number of requests);
* ``occupancy``                — mean active slots per decode step
  (== requests advanced per step; ``batch`` when the pool stays full).

``--check`` (default) also replays the wave through the retained
per-request oracle loop (``Engine.generate_sequential``) and asserts greedy
token-identity — the same contract tests/test_serve.py enforces — and
records the oracle's decode-step count for comparison.

``--traffic <profile.json>`` switches to the serving-tier benchmark: a
validated :class:`repro.serve.traffic.TrafficProfile` drives the engine
through ``Engine.serve`` (admission queue + virtual clock) and the payload
gains the latency-tier metrics CI trends — ``latency_p50/p99_ticks``,
``ttft_p50/p99_ticks``, ``goodput_tokens_per_tick`` — all denominated in
deterministic virtual ticks (1 tick = one pooled decode step), plus the
oracle-parity boolean. ``--page-size/--pool-pages`` serve it through the
paged KV cache.

    PYTHONPATH=src python benchmarks/serve_bench.py --out serve-bench.json
    PYTHONPATH=src python benchmarks/serve_bench.py --batch 8 --requests 32 \
        --max-new 16 --no-check
    PYTHONPATH=src python benchmarks/serve_bench.py \
        --traffic examples/traffic_steady.json --page-size 8 \
        --out serve-traffic.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def make_requests(n: int, prompt_len: int, max_new: int, temperature: float,
                  vocab: int, seed: int = 0):
    from repro.serve.engine import Request

    rng = np.random.RandomState(seed)
    return [
        Request(
            prompt=rng.randint(1, vocab, size=prompt_len).astype(np.int32),
            max_new_tokens=max_new,
            temperature=temperature,
        )
        for _ in range(n)
    ]


def traffic_main(args, cfg, model, params) -> int:
    """The --traffic serving-tier benchmark: profile-driven Engine.serve."""
    from repro.serve.engine import Engine
    from repro.serve.traffic import TrafficProfile, simulate

    profile = TrafficProfile.from_json(args.traffic)
    max_seq = args.max_seq or profile.max_rows
    if profile.max_rows > max_seq:
        raise SystemExit(
            f"profile {profile.name!r} can draw requests needing "
            f"{profile.max_rows} cache rows but --max-seq={max_seq}"
        )
    eng = Engine(model, params, batch=args.batch, max_seq=max_seq,
                 page_size=args.page_size, pool_pages=args.pool_pages)

    # untimed warmup absorbs prefill/decode/gather/scatter jit compilation;
    # deterministic fields are identical across runs by construction
    simulate(eng, profile, policy=args.policy, check=False)
    payload = None
    for _ in range(max(args.repeats, 1)):
        p = simulate(eng, profile, policy=args.policy, check=False)
        if payload is None or p["wall_s"] < payload["wall_s"]:
            payload = p
    if args.check:
        chk = simulate(eng, profile, policy=args.policy, check=True)
        payload["matches_sequential"] = chk["matches_sequential"]
        if profile.temperature <= 0 and not payload["matches_sequential"]:
            raise AssertionError(
                "greedy traffic-driven serving diverged from the "
                "sequential oracle"
            )
    payload = dict(arch=args.arch, batch=args.batch, max_seq=max_seq,
                   **payload)

    print(
        f"traffic {profile.name!r}: {payload['n_accepted']}/"
        f"{payload['n_requests']} served at batch={args.batch} "
        f"({args.policy}), p50/p99 latency "
        f"{payload['latency_p50_ticks']:.1f}/"
        f"{payload['latency_p99_ticks']:.1f} ticks, p50/p99 TTFT "
        f"{payload['ttft_p50_ticks']:.1f}/{payload['ttft_p99_ticks']:.1f}, "
        f"goodput {payload['goodput_tokens_per_tick']:.2f} tok/tick, "
        f"{payload['tokens_s']:.1f} tok/s",
        file=sys.stderr,
    )

    text = json.dumps(payload, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="smollm-135m",
                    help="config name (reduced for CPU; default smollm-135m)")
    ap.add_argument("--batch", type=int, default=4, help="slot-pool size")
    ap.add_argument("--requests", type=int, default=16,
                    help="number of requests in the wave")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="synthetic prompt length (tokens)")
    ap.add_argument("--max-new", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="slot cache capacity (default prompt+max_new)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=2,
                    help="timed repetitions (best-of; first run after the "
                         "untimed warmup that absorbs jit compilation)")
    ap.add_argument("--no-check", dest="check", action="store_false",
                    help="skip the sequential-oracle token-identity check")
    ap.add_argument("--out", default=None,
                    help="write JSON here (default: stdout)")
    ap.add_argument("--traffic", default=None, metavar="PROFILE.json",
                    help="serving-tier mode: drive Engine.serve with this "
                         "TrafficProfile (emits latency/TTFT/goodput)")
    ap.add_argument("--policy", default="fifo", choices=("fifo", "latency"),
                    help="admission policy for --traffic (default fifo)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="serve through the paged KV cache with this page "
                         "size (rows per page)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="shared page-pool size (default: the contiguous "
                         "footprint, batch * ceil(max_seq/page_size))")
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import init_compile_cache
    from repro.models.transformer import CallConfig, build_model
    from repro.serve.engine import Engine

    init_compile_cache()
    cfg = get_config(args.arch).reduced()
    model = build_model(cfg, CallConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))

    if args.traffic is not None:
        return traffic_main(args, cfg, model, params)

    max_seq = args.max_seq or args.prompt_len + args.max_new
    eng = Engine(model, params, batch=args.batch, max_seq=max_seq,
                 page_size=args.page_size, pool_pages=args.pool_pages)

    wave = lambda: make_requests(
        args.requests, args.prompt_len, args.max_new, args.temperature,
        cfg.vocab_size, seed=args.seed,
    )

    # untimed warmup absorbs prefill + decode-step jit compilation
    eng.generate(wave(), seed=args.seed)

    best_wall, stats = None, None
    for _ in range(max(args.repeats, 1)):
        t0 = time.perf_counter()
        done = eng.generate(wave(), seed=args.seed)
        wall = time.perf_counter() - t0
        if best_wall is None or wall < best_wall:
            best_wall, stats = wall, dict(eng.last_stats)
    assert all(r.done for r in done)

    gen = stats["generated_tokens"]
    steps = stats["decode_steps"]
    payload = dict(
        arch=args.arch,
        batch=args.batch,
        n_requests=args.requests,
        prompt_len=args.prompt_len,
        max_new_tokens=args.max_new,
        temperature=args.temperature,
        max_seq=max_seq,
        wall_s=best_wall,
        generated_tokens=gen,
        decode_steps=steps,
        prefills=stats["prefills"],
        tokens_s=gen / max(best_wall, 1e-12),
        # per decode-generated token (excludes the prefill-sampled token
        # each request gets), so the value is exactly 1/occupancy
        decode_steps_per_token=steps / max(gen - stats["prefills"], 1),
        occupancy=stats["occupancy"],
        requests_per_step=stats["occupancy"],  # == mean slots advanced/step
    )

    if args.check:
        t1 = time.perf_counter()
        ref = eng.generate_sequential(wave(), seed=args.seed)
        payload["sequential_wall_s"] = time.perf_counter() - t1
        # the oracle pays ~one decode step per token per request
        payload["sequential_decode_steps"] = sum(
            max(len(r.out_tokens) - 1, 0) for r in ref
        )
        match = all(a.out_tokens == b.out_tokens for a, b in zip(ref, done))
        payload["matches_sequential"] = match
        if args.temperature <= 0 and not match:
            raise AssertionError(
                "greedy continuous-batching output diverged from the "
                "sequential oracle"
            )

    print(
        f"served {args.requests} reqs x {args.max_new} tokens at "
        f"batch={args.batch}: {payload['tokens_s']:.1f} tok/s, "
        f"{steps} decode steps ({payload['decode_steps_per_token']:.3f} "
        f"steps/token, occupancy {payload['occupancy']:.2f})"
        + (f"; sequential oracle would pay "
           f"{payload['sequential_decode_steps']} steps"
           if "sequential_decode_steps" in payload else ""),
        file=sys.stderr,
    )

    text = json.dumps(payload, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
