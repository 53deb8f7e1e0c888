"""Benchmark orchestrator — one function per paper table + the roofline
collation.  Prints ``name,us_per_call,derived`` CSV lines per the repo
contract, then the human-readable tables.
"""

from __future__ import annotations

import time


def main() -> None:
    from benchmarks import (
        coldstart_bench,
        decode_bench,
        integration_bench,
        kernels_bench,
        mesh_bench,
        roofline,
        serving_bench,
        table1_loc,
        table2_bench,
        table2_latency,
    )

    csv_rows = []

    # -- Table 1: LoC (derived = reduction %) -------------------------------
    t0 = time.perf_counter()
    t1 = table1_loc.main()
    csv_rows.append(
        (
            "table1_loc",
            (time.perf_counter() - t0) * 1e6,
            f"reduction={t1['reduction']:.2%};paper={t1['paper_reduction']:.2%}",
        )
    )

    # -- Table 2: latency (derived = prop/ctool and naive/ctool @512) -------
    t0 = time.perf_counter()
    t2 = table2_latency.main()
    last = t2["layers"][-1]
    csv_rows.append(
        (
            "table2_latency",
            (time.perf_counter() - t0) * 1e6,
            f"prop/ctool={last['prop/ctool']:.3f};naive/ctool={last['naive/ctool']:.1f};"
            f"toycar_naive/ctool={t2['toycar']['naive/ctool']:.1f}",
        )
    )

    # -- Table 2 at model scale: zoo x modes x accelerators -------------------
    t0 = time.perf_counter()
    zoo = table2_bench.main(["--smoke"])
    csv_rows.append(
        (
            "table2_model_zoo",
            (time.perf_counter() - t0) * 1e6,
            f"cells={len(zoo['rows'])}",
        )
    )

    # -- serving: batched plans vs per-sample loop ----------------------------
    t0 = time.perf_counter()
    serving = serving_bench.main(["--smoke"])
    csv_rows.append(
        (
            "serving_batched_vs_loop",
            (time.perf_counter() - t0) * 1e6,
            f"cells={len(serving['rows'])};"
            f"best_speedup={serving['summary']['best_speedup_req_s']:.2f}x",
        )
    )

    # -- decode: continuous batching vs sequential prefill-per-request --------
    t0 = time.perf_counter()
    decode = decode_bench.main(["--smoke"])
    csv_rows.append(
        (
            "decode_continuous_vs_sequential",
            (time.perf_counter() - t0) * 1e6,
            f"cells={len(decode['rows'])};"
            f"best_speedup={decode['summary']['best_speedup_tokens_per_s']:.2f}x",
        )
    )

    # -- cold start: AOT artifact load vs full compile ------------------------
    t0 = time.perf_counter()
    cold = coldstart_bench.main(["--smoke"])
    csv_rows.append(
        (
            "coldstart_artifact_vs_compile",
            (time.perf_counter() - t0) * 1e6,
            f"cells={len(cold['rows'])};"
            f"best_load_speedup={cold['summary']['best_load_speedup']:.1f}x",
        )
    )

    # -- mesh: sharded plans vs the single-device plan ------------------------
    t0 = time.perf_counter()
    mesh = mesh_bench.main(["--smoke"])
    best = max(r["modeled_speedup_at_4"] for r in mesh["rows"])
    csv_rows.append(
        (
            "mesh_sharded_vs_single_device",
            (time.perf_counter() - t0) * 1e6,
            f"models={len(mesh['rows'])};"
            f"best_modeled_speedup_at_4={best:.2f}x;"
            f"passing_gate={len(mesh['summary']['models_passing_gate'])}",
        )
    )

    # -- kernel micro-bench ---------------------------------------------------
    for name, us, derived in kernels_bench.main():
        csv_rows.append((name, us, derived))

    # -- schedule-cache: cold vs warm integrate() compiles --------------------
    for name, us, derived in integration_bench.main():
        csv_rows.append((name, us, derived))

    # -- roofline collation ----------------------------------------------------
    t0 = time.perf_counter()
    cells = roofline.main()
    ok = sum(1 for c in cells if c.get("status") == "ok")
    csv_rows.append(
        ("roofline_cells", (time.perf_counter() - t0) * 1e6, f"cells_ok={ok}")
    )

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
