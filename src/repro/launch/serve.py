"""Batched serving driver: prefill + decode over a synthetic request pool,
or accelerator-compiled zoo-model serving through the ``repro.compile()``
front door with a micro-batching request queue.

    # LM serving (JAX engine)
    PYTHONPATH=src python -m repro.launch.serve --arch musicgen_medium --smoke \
        --requests 16 --batch 4 --new-tokens 16

    # accelerator serving: batched ExecutionPlans + micro-batched dispatch
    PYTHONPATH=src python -m repro.launch.serve --zoo mlp_tiny \
        --target gemmini:optimized --requests 256 --batch 16
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _percentile(samples: list[float], pct: float) -> float:
    return float(np.percentile(np.asarray(samples), pct)) if samples else 0.0


def serve_zoo(args) -> None:
    """Serve a model-zoo network on an accelerator target: ONE batched
    ``repro.compile`` call (one ExecutionPlan per batch bucket), then a
    micro-batching queue that collects up to ``--batch`` requests (or a
    deadline) and dispatches each batch as one bucketed execution."""
    import repro
    from repro.core.zoo import get_model
    from repro.serve import MicroBatcher

    model = get_model(args.zoo)
    target = repro.Target.parse(
        args.target, batch_size=args.batch, devices=getattr(args, "devices", 1)
    )
    artifact = getattr(args, "artifact", None)
    if artifact:
        # AOT boot: restore the batched module from a saved artifact — no
        # compile, no DSE, no pass pipeline at startup
        t0 = time.perf_counter()
        module = repro.load(artifact)
        t_boot = time.perf_counter() - t0
        if not isinstance(module, repro.BatchedModule):
            raise SystemExit(
                f"--artifact {artifact} holds a single-shape module; the "
                f"serving loop needs a batched artifact (save a module "
                f"compiled with batch_buckets / Target(batch_size=...))"
            )
        boot_how = "loaded artifact"
    else:
        # batch_size=1 compiles the classic single-shape module; the
        # serving loop always wants the batched surface, so pin an
        # explicit unit bucket
        options = (
            repro.CompileOptions(batch_buckets=(1,))
            if args.batch <= 1
            else None
        )
        t0 = time.perf_counter()
        module = repro.compile(args.zoo, target, options=options)
        t_boot = time.perf_counter() - t0
        boot_how = "compiled"
    buckets = module.bucket_sizes()
    if getattr(args, "save_artifact", None):
        repro.save(module, args.save_artifact)
        print(f"[serve] saved compile artifact to {args.save_artifact}")

    # warmup: run every bucket once (full chunks, so each bucket's plan,
    # arena, and executor scratch are touched) — the measured window never
    # pays first-call costs, and a fast target with few requests cannot
    # end up timing an empty window
    for b in buckets:
        module.run_many([model.feeds(seed=0)] * b)

    traffic = [model.feeds(seed=s) for s in range(args.requests)]
    latencies: list[float] = []
    t0 = time.perf_counter()
    with MicroBatcher(
        module, max_batch=args.batch, max_delay_s=args.deadline_ms / 1e3
    ) as mb:
        pending = [(time.perf_counter(), mb.submit(feeds)) for feeds in traffic]
        outs = []
        for t_submit, fut in pending:
            outs.append(fut.result())
            latencies.append(time.perf_counter() - t_submit)
        stats = mb.stats
    dt = max(time.perf_counter() - t0, 1e-9)  # guard: never divide by zero

    n = max(len(outs), 1)
    cycles = module.modeled_cycles()  # largest bucket's plan
    mesh_note = ""
    if target.devices > 1:
        dp, mp = target.resolved_mesh
        mesh_note = f" on a (data={dp}, model={mp}) mesh"
    print(
        f"[serve] {model.name} on {target.describe()}: {boot_how} "
        f"{len(buckets)} bucket plans {list(buckets)}{mesh_note} in "
        f"{t_boot * 1e3:.1f} ms (cold start)"
    )
    print(
        f"[serve] {n} requests in {dt:.3f}s ({n / dt:.0f} req/s); latency "
        f"p50 {_percentile(latencies, 50) * 1e6:.1f} us / "
        f"p99 {_percentile(latencies, 99) * 1e6:.1f} us; "
        f"{stats.batches} dispatches, mean batch {stats.mean_batch():.1f}"
    )
    print(
        f"[serve] modeled cycles/request at batch {buckets[-1]}: "
        f"{cycles['total'] / buckets[-1]:,.0f} "
        f"(accel {cycles['accel'] / buckets[-1]:,.0f} / "
        f"host {cycles['host'] / buckets[-1]:,.0f} / "
        f"comm {cycles.get('comm', 0.0) / buckets[-1]:,.0f})"
    )
    if outs:
        print(f"[serve] sample output: {np.asarray(outs[0][0]).ravel()[:8]}")


def serve_decode(args) -> None:
    """Serve a decode-zoo model through the continuous-batching engine:
    two compiled ExecutionPlans (prefill + batched decode step) over a
    block-based KV pool, finished slots backfilled from the queue."""
    import repro
    from repro.core.zoo import get_decode_model
    from repro.serve import ContinuousBatchingEngine, EngineConfig, random_requests

    model = get_decode_model(args.zoo)
    target = repro.Target.parse(args.target)
    prompt_len = min(args.prompt_len, model.max_len - args.new_tokens)
    if prompt_len < 1:
        raise SystemExit(
            f"--new-tokens {args.new_tokens} leaves no room for a prompt "
            f"inside the {model.max_len}-row KV cache"
        )
    cfg = EngineConfig(
        batch=args.batch,
        prompt_len=prompt_len,
        max_new_tokens=args.new_tokens,
    )
    t0 = time.perf_counter()
    engine = ContinuousBatchingEngine(model, target, cfg)
    t_boot = time.perf_counter() - t0
    requests = random_requests(model, args.requests, cfg.prompt_len, seed=0)
    report = engine.run(requests)
    print(
        f"[serve] {model.name} on {target.describe()}: continuous batching, "
        f"{cfg.batch} decode slots, compiled prefill+decode plans in "
        f"{t_boot * 1e3:.1f} ms (cold start)"
    )
    print(
        f"[serve] {len(report.requests)} requests, {report.total_new_tokens} tokens "
        f"in {report.wall_s:.3f}s ({report.tokens_per_s:.0f} tok/s); "
        f"{report.decode_steps} decode steps, {report.prefills} prefills"
    )
    print(
        f"[serve] block pool: {report.n_blocks} blocks x {report.block_size} "
        f"rows, peak occupancy {report.peak_occupancy:.1%}"
    )
    print("[serve] sample tokens:", requests[0].tokens[:8])


def serve_lm(args) -> None:
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.models import lm
    from repro.serve import ServeConfig, ServingEngine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend:
        raise SystemExit(
            f"{cfg.name} needs frontend embeddings; use a text arch for the demo"
        )
    params = lm.init_lm(jax.random.key(0), cfg)
    engine = ServingEngine(
        cfg,
        params,
        ServeConfig(
            batch=args.batch,
            max_len=args.prompt_len + args.new_tokens + 1,
            max_new_tokens=args.new_tokens,
        ),
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab, size=(args.prompt_len,)).astype(np.int32)
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    done = engine.generate(prompts)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in done)
    print(
        f"[serve] {cfg.name}: {len(done)} requests, {total_tokens} tokens "
        f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)"
    )
    print("[serve] sample output:", done[0].output[:16])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM architecture to serve (JAX engine)")
    ap.add_argument("--zoo", help="zoo model to serve on an accelerator target")
    ap.add_argument(
        "--target",
        default="gemmini:optimized",
        help="accelerator[:mode] for --zoo (Target.parse syntax)",
    )
    ap.add_argument(
        "--artifact",
        help="boot --zoo serving from a saved AOT compile artifact "
        "(repro.load) instead of compiling at startup",
    )
    ap.add_argument(
        "--save-artifact",
        help="after boot, save the (batched) compiled module as an AOT "
        "artifact at this path (repro.save)",
    )
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument(
        "--devices",
        type=int,
        default=1,
        help="mesh size for --zoo: compile one ExecutionPlan per shard of "
        "a (data, model) mesh and serve through the sharded executor",
    )
    ap.add_argument(
        "--deadline-ms",
        type=float,
        default=2.0,
        help="micro-batching deadline: max wait after the oldest queued "
        "request before dispatching a partial batch (--zoo mode)",
    )
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    if bool(args.arch) == bool(args.zoo):
        raise SystemExit("pass exactly one of --arch (LM) or --zoo (accelerator)")
    if args.requests < 1:
        raise SystemExit("--requests must be >= 1")
    if args.batch < 1:
        raise SystemExit("--batch must be >= 1")
    from repro.launch.compile_cache import use_persistent_compile_cache

    use_persistent_compile_cache()
    if args.zoo:
        from repro.core.zoo import decode_model_names

        if args.zoo in decode_model_names():
            serve_decode(args)
        else:
            serve_zoo(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
