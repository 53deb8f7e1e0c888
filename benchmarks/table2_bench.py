"""Table-2 model-zoo benchmark: whole networks x three backends x
accelerators, through the planned graph executor.

For every (zoo model, accelerator, mode) cell this harness reports

  * **modeled cycles** (accel / host / total) from the compiled module's
    cycle model — the paper's Table-2 axis: proposed ~= C toolchain,
    naive BYOC blown up by unfolded preprocessing + unfused epilogues;
  * **wall-clock run latency** of the planned executor (``run_many``)
    over a batch of feeds.

Functional correctness is asserted before any timing: every cell must be
bit-exact with the reference semantics of the hand-built golden graph
(``ir.execute_graph``).

Results are written to ``BENCH_table2.json``.  ``--smoke`` runs a reduced
matrix with minimal reps (CI); every run asserts the paper's cycle
orderings.
"""

from __future__ import annotations

import argparse
import json
import platform
import time
from pathlib import Path

import numpy as np

import repro
from repro.core import ir
from repro.core.pipeline import PUBLIC_MODES
from repro.core.zoo import get_model, model_names

SMOKE_MODELS = ("mlp_tiny", "qcnn")
SMOKE_ACCELERATORS = {"gemmini", "edge_npu"}


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_cell(model, acc: str, mode: str, *, smoke: bool) -> dict:
    # the front door: zoo name -> traced-JAX frontend -> compiled module.
    # Backends are memoized per accelerator (mode is compile-time), so the
    # in-memory scheduler memo is shared across the whole sweep.
    mod = repro.compile(model.name, repro.Target(acc, mode=mode, cache=False))
    feeds = model.feeds(seed=1)

    # -- correctness gate ---------------------------------------------------
    planned = mod.run(feeds)
    # reference semantics come from the HAND-BUILT golden graph: this
    # also pins traced-frontend parity on every benchmark run
    ref = ir.execute_graph(model.build(), feeds)
    for p, r in zip(planned, ref):
        assert np.array_equal(np.asarray(p), r), (
            f"{model.name}/{acc}/{mode}: executor diverges "
            f"from golden graph reference semantics"
        )

    cycles = mod.modeled_cycles()

    # -- wall clock: size the batch so one measurement is ~0.2s -------------
    t0 = time.perf_counter()
    mod.run(feeds)
    t_single = max(time.perf_counter() - t0, 1e-6)
    target_s = 0.02 if smoke else 0.2
    n_feeds = int(min(max(target_s / t_single, 3), 300))
    feeds_list = [model.feeds(seed=s) for s in range(n_feeds)]
    reps = 2 if smoke else 5
    mod.run_many(feeds_list)  # warm
    t_planned = _best_of(lambda: mod.run_many(feeds_list), reps) / n_feeds
    return {
        "model": model.name,
        "accelerator": acc,
        "mode": mode,
        "modeled_cycles": cycles,
        "planned_us": t_planned * 1e6,
        "n_feeds": n_feeds,
        "reps": reps,
        # per-pass rewrite/timing instrumentation from the PassManager run
        # that lowered this cell (lands in the uploaded CI artifact)
        "passes": mod.pass_report.to_dict() if mod.pass_report else None,
    }


def run(models: list[str], *, smoke: bool, out: Path) -> dict:
    rows: list[dict] = []
    for name in models:
        model = get_model(name)
        accels = [
            a
            for a in model.accelerators
            if not smoke or a in SMOKE_ACCELERATORS
        ]
        for acc in accels:
            for mode in PUBLIC_MODES:
                row = bench_cell(model, acc, mode, smoke=smoke)
                rows.append(row)
                print(
                    f"{row['model']:>18} {row['accelerator']:>8} {row['mode']:>11} "
                    f"cycles={row['modeled_cycles']['total']:>12,.0f} "
                    f"planned={row['planned_us']:>9.1f}us"
                )

    pass_totals: dict[str, dict[str, float]] = {}
    for r in rows:
        for p in (r.get("passes") or {}).get("passes", ()):
            agg = pass_totals.setdefault(
                p["name"], {"rewrites": 0, "duration_ms": 0.0}
            )
            agg["rewrites"] += p["rewrites"]
            agg["duration_ms"] += p["duration_ms"]
    summary = {
        "pass_totals": pass_totals,
    }
    payload = {
        "bench": "table2_model_zoo",
        "smoke": smoke,
        "host": platform.machine(),
        "rows": rows,
        "summary": summary,
    }
    out.write_text(json.dumps(payload, indent=2))
    print(f"\nwrote {out} ({len(rows)} cells)")

    # -- Table-2 claims ------------------------------------------------------
    by_cell = {(r["model"], r["accelerator"], r["mode"]): r for r in rows}
    for (model, acc, mode), r in by_cell.items():
        if mode != "optimized":
            continue
        ctool = by_cell.get((model, acc, "baseline"))
        naive = by_cell.get((model, acc, "naive"))
        if ctool:
            ratio = r["modeled_cycles"]["total"] / ctool["modeled_cycles"]["total"]
            assert ratio < 1.2, (
                f"{model}/{acc}: optimized must match the C-toolchain baseline "
                f"(got {ratio:.2f}x)"
            )
        if naive:
            blowup = naive["modeled_cycles"]["total"] / r["modeled_cycles"]["total"]
            assert blowup > 1.5, (
                f"{model}/{acc}: naive BYOC must be substantially slower "
                f"(got {blowup:.2f}x)"
            )
    return payload


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="reduced matrix + minimal reps for CI",
    )
    ap.add_argument(
        "--models",
        nargs="*",
        default=None,
        help=f"zoo models to run (default: all; available: {model_names()})",
    )
    ap.add_argument(
        "--out",
        type=Path,
        default=Path("BENCH_table2.json"),
        help="output JSON path",
    )
    args = ap.parse_args(argv)
    models = args.models or (
        [m for m in SMOKE_MODELS] if args.smoke else model_names()
    )
    for m in models:
        get_model(m)  # fail fast on typos
    return run(models, smoke=args.smoke, out=args.out)


if __name__ == "__main__":
    main()
