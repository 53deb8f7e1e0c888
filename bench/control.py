"""Readings that set and prove the limits of the comparison.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13 ...

Runs the cell once per seed in one process, in three forms, and prints the
check's numbers of each run as one JSON line:

* ``program`` — the cell as ``bench/run.py`` runs it (the lower readings);
* ``control`` — the plain reference with its weights in int4, the next
  precision below the configuration's int8, put in the program's place
  behind the same generator, batcher and check (the upper readings; it
  has to come out not correct);
* ``fault`` — the program with one element of one answer in every call
  altered where it is produced (it has to come out not correct);
* ``half_batch`` — the program run over half of every call's samples, the
  other half answered with that half's answers (not correct either);
* ``bf16_answers`` — the program with every float answer rounded to
  bfloat16, a lower precision than a float32 answer: for configurations
  with float answers, whose limits it has to fail (an int8 answer is
  exact in bfloat16 and is left as it is).

``--forms`` picks which.  Needs the chip, as ``bench/run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]


class ReferenceModule:
    """The configuration's reference at ``weight_bits``, in the program's
    place: ``run_many`` answers each sample as the compiled module would."""

    def __init__(self, cell, params, weight_bits: int):
        self.cell, self.params, self.bits = cell, params, weight_bits

    def run_many(self, feeds_list):
        x = np.stack([f["x"] for f in feeds_list])
        out = self.cell.ref.reference(self.cell.config, self.params, x, weight_bits=self.bits)
        return [[o] for o in out]


class AlteredAnswers:
    """The compiled module with one element of the first answer of every
    call changed: a wrong answer where it is produced.  An integer element
    has its lowest bit flipped; a float one moves by more than the
    answer's largest magnitude."""

    def __init__(self, module):
        self.module = module

    def run_many(self, feeds_list):
        outs = self.module.run_many(feeds_list)
        first = outs[0][0].copy()
        if np.issubdtype(first.dtype, np.integer):
            first.flat[0] ^= 1
        else:
            first.flat[0] += 1 + np.abs(first).max()
        outs[0] = [first] + list(outs[0][1:])
        return outs


class RoundedAnswers:
    """The compiled module with every float answer rounded to bfloat16 and
    back to its own type."""

    def __init__(self, module):
        self.module = module

    def run_many(self, feeds_list):
        import jax.numpy as jnp

        def rounded(o):
            o = np.asarray(o)
            if not np.issubdtype(o.dtype, np.floating):
                return o
            return o.astype(jnp.bfloat16).astype(o.dtype)

        return [[rounded(o) for o in outs] for outs in self.module.run_many(feeds_list)]


class HalfBatch:
    """The compiled module run over the first half of every call's samples
    only; the rest are answered with the answers of that half, in turn: a
    batch of which half was left out."""

    def __init__(self, module):
        self.module = module

    def run_many(self, feeds_list):
        half = (len(feeds_list) + 1) // 2
        outs = self.module.run_many(feeds_list[:half])
        return [outs[i % half] for i in range(len(feeds_list))]


def control(cell):
    return lambda module, params: ReferenceModule(cell, params, weight_bits=4)


def fault(cell):
    return lambda module, params: AlteredAnswers(module)


def half_batch(cell):
    return lambda module, params: HalfBatch(module)


def bf16_answers(cell):
    return lambda module, params: RoundedAnswers(module)


FORMS = {"program": None, "control": control, "fault": fault, "half_batch": half_batch,
         "bf16_answers": bf16_answers}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--forms", nargs="+", default=list(FORMS), choices=list(FORMS))
    args = p.parse_args(argv)
    from bench import run

    run._setup_env()
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    for form in args.forms:
        for seed in args.seeds:
            wrap = FORMS[form](cell) if FORMS[form] else None
            result, _ = run.measure(cell, seed, args.seconds, False, wrap=wrap)
            line = {"form": form, "workload": cell.name, "seed": seed, "correct": result["correct"],
                    "attempted": result["attempted"], "check": result["check"],
                    "metrics": result["metrics"]}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
