"""Cold-start benchmark: AOT artifact load vs full compile.

Fleet question this answers: when N serving replicas boot the same model,
what does each replica pay?  Two ways:

  * **compile** — the full front door: trace + pass pipeline + extended-CoSA
    DSE + plan build (fresh backend, no schedule cache);
  * **load** — ``repro.load()`` of a content-addressed artifact saved once
    by the fleet leader: zero DSE sweeps, zero measurements, zero rewrite
    fires (asserted on the restored backend's counters).

Correctness gates the timing: loaded modules must be bit-exact with the
compiled ones, and the restored backend counters must read zero work.

Results land in ``BENCH_coldstart.json``.  ``--smoke`` runs one cell (CI);
``--gate`` enforces the cold-start speedup.
"""

from __future__ import annotations

import argparse
import json
import platform
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

import repro
from repro.core.zoo import get_model, model_names

ACCELERATORS = ("gemmini", "edge_npu")
SMOKE_MODELS = ("qcnn",)  # big enough that compile time dwarfs load time
SMOKE_ACCELERATORS = ("gemmini",)


def _assert_zero_work(module) -> None:
    for mod in (
        [module.bucket_module(b) for b in module.bucket_sizes()]
        if isinstance(module, repro.BatchedModule)
        else [module]
    ):
        assert mod.backend.scheduler.n_solver_calls == 0, "load ran DSE"
        assert mod.backend.n_measurements == 0, "load ran measurements"


def bench_coldstart_cell(model_name: str, acc: str, *, reps: int) -> dict:
    """Time compile-from-scratch vs ``repro.load`` for one zoo cell."""
    model = get_model(model_name)
    target = repro.Target(acc, mode="optimized", cache=False)
    opts = repro.CompileOptions(fresh_backend=True)

    compile_s = []
    module = None
    for _ in range(reps):
        t0 = time.perf_counter()
        module = repro.compile(model_name, target, options=opts)
        compile_s.append(time.perf_counter() - t0)

    art_dir = Path(tempfile.mkdtemp(prefix="repro-coldstart-"))
    try:
        art = art_dir / "artifact"
        repro.save(module, art)
        load_s = []
        loaded = None
        for _ in range(reps):
            t0 = time.perf_counter()
            loaded = repro.load(art)
            load_s.append(time.perf_counter() - t0)
        # gates: zero work on load, bit-exact with the compiled module
        _assert_zero_work(loaded)
        feeds = model.feeds(seed=3)
        for a, b in zip(module.run(feeds), loaded.run(feeds)):
            assert np.array_equal(a, b), (
                f"{model_name}/{acc}: loaded module diverges from compiled"
            )
    finally:
        shutil.rmtree(art_dir, ignore_errors=True)

    compile_ms = min(compile_s) * 1e3
    load_ms = min(load_s) * 1e3
    return {
        "model": model_name,
        "accelerator": acc,
        "compile_ms": compile_ms,
        "load_ms": load_ms,
        "load_speedup": compile_ms / max(load_ms, 1e-9),
    }


def run(
    models: list[str],
    accelerators: tuple[str, ...],
    *,
    smoke: bool,
    gate: bool,
    out: Path,
) -> dict:
    reps = 2 if smoke else 4

    rows = []
    for name in models:
        model = get_model(name)
        for acc in accelerators:
            if acc not in model.accelerators:
                continue
            row = bench_coldstart_cell(name, acc, reps=reps)
            rows.append(row)
            print(
                f"{row['model']:>18} {row['accelerator']:>8} "
                f"compile={row['compile_ms']:>8.1f} ms "
                f"load={row['load_ms']:>7.1f} ms "
                f"({row['load_speedup']:>5.1f}x)"
            )

    best = max(rows, key=lambda r: r["load_speedup"])
    payload = {
        "bench": "coldstart_artifact_vs_compile",
        "smoke": smoke,
        "host": platform.machine(),
        "rows": rows,
        "summary": {
            "best_load_speedup": best["load_speedup"],
            "best_cell": (best["model"], best["accelerator"]),
        },
    }
    out.write_text(json.dumps(payload, indent=2))
    print(
        f"\nwrote {out} ({len(rows)} cold-start cells); best load speedup "
        f"{best['load_speedup']:.1f}x on {best['model']}/{best['accelerator']}"
    )

    if gate:
        # the cold-start claim is host-independent: loading skips the DSE
        # and the pass pipeline entirely
        for row in rows:
            assert row["load_speedup"] >= 1.2, (
                f"artifact load must beat full compile on "
                f"{row['model']}/{row['accelerator']} "
                f"(got {row['load_speedup']:.2f}x)"
            )
        assert best["load_speedup"] >= 2.0, (
            f"best artifact-load speedup must reach >= 2x "
            f"(got {best['load_speedup']:.2f}x on "
            f"{best['model']}/{best['accelerator']})"
        )
    return payload


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="one cell with few reps (CI)")
    ap.add_argument("--gate", action="store_true",
                    help="enforce the cold-start speedup")
    ap.add_argument("--models", nargs="*", default=None,
                    help=f"zoo models (default: all; available: {model_names()})")
    ap.add_argument("--out", type=Path, default=Path("BENCH_coldstart.json"))
    args = ap.parse_args(argv)
    models = args.models or list(SMOKE_MODELS if args.smoke else model_names())
    accelerators = SMOKE_ACCELERATORS if args.smoke else ACCELERATORS
    for m in models:
        get_model(m)  # fail fast on typos
    return run(models, accelerators, smoke=args.smoke, gate=args.gate,
               out=args.out)


if __name__ == "__main__":
    main()
