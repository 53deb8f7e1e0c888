"""Serving a zoo model through the planned graph executor.

Compiles a model-zoo network once through the ``repro.compile()`` front
door (the zoo name routes through the traced-JAX frontend), then drives
repeated inference with ``run_many`` — the compiled ``ExecutionPlan`` (flat
step list, slot-indexed buffer arena, pre-padded constant weight panels) is
built at compile time and reused across every call.  Every answer is
checked bit-exact against the reference semantics of the hand-built golden
graph (``ir.execute_graph``).

    PYTHONPATH=src python examples/serve_zoo.py [model] [accelerator:mode]
"""

import sys
import time

import numpy as np

import repro
from repro.core import ir
from repro.core.zoo import get_model, model_names


def main(model_name: str = "mlp_tiny", target: str = "gemmini:optimized"):
    model = get_model(model_name)
    module = repro.compile(
        model_name, repro.Target.parse(target, cache=False)
    )

    traffic = [model.feeds(seed=s) for s in range(256)]
    planned = module.run_many(traffic)
    golden = model.build()
    assert all(
        np.array_equal(np.asarray(p[0]), ir.execute_graph(golden, feeds)[0])
        for p, feeds in zip(planned, traffic)
    ), "planned executor must be bit-exact with the golden graph"

    t0 = time.perf_counter()
    module.run_many(traffic)
    t_planned = time.perf_counter() - t0

    plan = module.plan
    print(f"model={model.name} ({model.description})")
    print(
        f"plan: {len(plan.steps)} steps, {len(plan.const_slots)} materialized "
        f"consts, {plan.n_slots} arena slots"
    )
    print(
        f"{len(traffic)} requests: planned {t_planned / len(traffic) * 1e6:8.1f} us/call"
    )
    print(f"modeled cycles: {module.modeled_cycles()}")


if __name__ == "__main__":
    name = sys.argv[1] if len(sys.argv) > 1 else "mlp_tiny"
    if name in ("-h", "--help"):
        print(__doc__)
        print("models:", ", ".join(model_names()))
        raise SystemExit(0)
    main(name, sys.argv[2] if len(sys.argv) > 2 else "gemmini:optimized")
