"""The comparison that decides ``correct``.

What is compared is what the timed path answered: every answer of an open
loop, and the calls a closed loop kept (drawn from the seed).  Each answer
is set against the configuration's plain reference
(``bench/configs/<config>_ref.py``) run on the same input, after the
window has closed and the program's state is freed, in blocks of samples.

The configuration's ``check.limits`` names the numbers compared, each with
its limit, and ``check.why`` gives the reason for each limit.  A name this
module does not know is an error.  ``unanswered`` is always named, and at
least one number that compares answers:

* ``unanswered`` — requests whose answer never came or was an error;
* ``wrong_elements`` — output elements that differ from the reference (an
  answer of the wrong shape or type counts every element).  For answers
  the program and the reference compute exactly, as in integer models with
  power-of-two requantize scales; its limit is then 0;
* ``max_rel_error`` — over the answers, the largest ``|got - want|`` over
  the root mean square of that answer's reference.  For float answers:
  one element gone wrong shows in it however large the answer is;
* ``rel_l2_error`` — over the answers, the largest ``||got - want||_2 /
  ||want||_2``.  For float answers whose error is spread over every
  element, as a lower precision spreads it.

A float measure reads ``inf`` for an answer of the wrong shape or type,
or with a value that is not finite, and for an answer that differs from an
all-zero reference.  A config that names a float measure shows in
``check.why`` that its int4 control (``bench/control.py``) and an answer
rounded to a lower precision fail its limit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

#: samples per reference call
REF_BLOCK = 4096


def _same_kind(g: np.ndarray, w: np.ndarray) -> bool:
    return g.shape == w.shape and g.dtype == w.dtype


def _wrong_elements(pairs) -> int:
    return sum(int(np.count_nonzero(g != w)) if _same_kind(g, w) else w.size for g, w in pairs)


def _worst_ratio(pairs, err, scale) -> float:
    """The largest ``err(got - want) / scale(want)`` over the answers."""
    worst = 0.0
    for g, w in pairs:
        if not _same_kind(g, w) or not np.all(np.isfinite(g)):
            return math.inf
        d = err(g.astype(np.float64) - w.astype(np.float64))
        if d > 0:
            s = scale(w.astype(np.float64))
            worst = max(worst, d / s if s > 0 else math.inf)
    return float(worst)


#: every number an answer comparison can give, from (got, want) pairs
MEASURES = {
    "wrong_elements": _wrong_elements,
    "max_rel_error": lambda pairs: _worst_ratio(
        pairs, lambda d: np.abs(d).max(), lambda w: np.sqrt(np.mean(w * w))),
    "rel_l2_error": lambda pairs: _worst_ratio(pairs, np.linalg.norm, np.linalg.norm),
}


def _json_number(v):
    """JSON has no infinity: a non-finite number is written as its name."""
    return v if math.isfinite(v) else str(v)


@dataclass
class Verdict:
    numbers: dict  # name -> (value, limit)
    compared: int

    @property
    def correct(self) -> bool:
        return self.compared > 0 and all(v <= lim for v, lim in self.numbers.values())

    def lines(self) -> list[str]:
        out = [f"check: {name} {v} limit {lim}" for name, (v, lim) in self.numbers.items()]
        return out + [f"check: answers compared {self.compared}, correct {self.correct}"]

    def as_json(self) -> dict:
        return {name: {"value": _json_number(v), "limit": lim}
                for name, (v, lim) in self.numbers.items()}


def reference_blocks(ref, cfg: dict, params: dict, x: np.ndarray) -> np.ndarray:
    block = REF_BLOCK if x.shape[0] > REF_BLOCK else x.shape[0]
    return np.concatenate(
        [ref.reference(cfg, params, x[i : i + block]) for i in range(0, len(x), block)]
    )


def compare(got: list, want: np.ndarray, limits: dict) -> Verdict:
    """``got[i]`` is answer i (an array, or None where none came);
    ``want[i]`` is the reference's.  ``limits`` names the numbers compared
    (see the module's docstring)."""
    unknown = set(limits) - set(MEASURES) - {"unanswered"}
    if unknown:
        raise ValueError(f"check.limits names unknown measures {sorted(unknown)}; "
                         f"known: unanswered, {', '.join(MEASURES)}")
    if "unanswered" not in limits or len(limits) < 2:
        raise ValueError("check.limits names unanswered and at least one measure of the answers")
    pairs = [(np.asarray(g), w) for g, w in zip(got, want) if g is not None]
    unanswered = sum(g is None for g, _ in zip(got, want))
    numbers = {}
    for name, limit in limits.items():
        value = unanswered if name == "unanswered" else MEASURES[name](pairs)
        numbers[name] = (value, limit)
    return Verdict(numbers, len(got) - unanswered)
