"""The comparison that decides ``correct``.

What is compared is what the timed path answered: every answer of an open
loop, and the calls a closed loop kept (drawn from the seed).  Each answer
is set against the configuration's plain reference
(``bench/configs/<config>_ref.py``) run on the same input, after the
window has closed and the program's state is freed, in blocks of samples.

Numbers compared, each against its limit from the configuration's
``check.limits``:

* ``wrong_elements`` — output elements that differ from the reference
  (an answer of the wrong shape or type counts every element);
* ``unanswered`` — requests whose answer never came or was an error.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: samples per reference call
REF_BLOCK = 4096


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
        return {name: {"value": v, "limit": lim} for name, (v, lim) in self.numbers.items()}


def reference_blocks(ref, cfg: dict, params: dict, x: np.ndarray) -> np.ndarray:
    block = REF_BLOCK if x.shape[0] > REF_BLOCK else x.shape[0]
    return np.concatenate(
        [ref.reference(cfg, params, x[i : i + block]) for i in range(0, len(x), block)]
    )


def compare(got: list, want: np.ndarray, limits: dict) -> Verdict:
    """``got[i]`` is answer i (an array, or None where none came);
    ``want[i]`` is the reference's."""
    wrong = unanswered = 0
    for g, w in zip(got, want):
        if g is None:
            unanswered += 1
            continue
        g = np.asarray(g)
        if g.shape != w.shape or g.dtype != w.dtype:
            wrong += w.size
        else:
            wrong += int(np.count_nonzero(g != w))
    numbers = {
        "wrong_elements": (wrong, limits["wrong_elements"]),
        "unanswered": (unanswered, limits["unanswered"]),
    }
    return Verdict(numbers, len(got) - unanswered)
