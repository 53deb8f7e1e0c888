"""The one traffic generator: reads a mix's parameters and drives a
compiled module with them.

A mix (``bench/traffic/<name>.json``) is either

* ``"loop": "open"`` — requests of one sample each, due on a schedule
  drawn from the seed (``"arrivals": "poisson"`` at ``rate_per_s``),
  submitted to
  ``repro.serve.MicroBatcher`` (``max_batch``, ``max_delay_s``) whatever
  the server's state; each request is timed from its due time to its
  answer; or
* ``"loop": "closed"`` — one caller issuing ``run_many`` over
  ``samples_per_call`` samples, the next call when the last returns.

Both take the module's batch buckets from ``buckets`` and, for sequence
models, the sequence length from ``seq_len``.  Inputs are drawn from the
seed before the window; nothing is generated inside it.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

#: distinct call inputs a closed loop cycles through
CLOSED_POOL = 8
#: calls a closed loop keeps, drawn from the seed, for the comparison
CLOSED_KEEP = 2
#: seconds an open loop waits past its window for answers still due
ANSWER_GRACE_S = 60.0
#: head start between submitting the plan and the first due time
OPEN_LEAD_S = 0.05


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def arrivals(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times, in seconds from the window's start, of every request in
    the window: a Poisson process at ``rate_per_s``.

    Every seed gets the same set of gaps between requests, in its own
    order: the gaps are the exponential distribution's quantiles at the
    midpoints of ``n`` equal steps, ``n`` the expected number of requests,
    scaled so that they fill the window.  So the count and the burstiness
    of the load do not change from seed to seed; only which gaps come
    together does."""
    n = max(1, round(seconds * float(traffic["rate_per_s"])))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


@dataclass
class Calls:
    """Every ``run_many`` the window issued: host start and end
    (``perf_counter`` seconds) and samples per call."""

    start: list = field(default_factory=list)
    end: list = field(default_factory=list)
    samples: list = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def add(self, s: float, e: float, n: int) -> None:
        with self.lock:
            self.start.append(s)
            self.end.append(e)
            self.samples.append(n)


class TimedModule:
    """The module handed to MicroBatcher, wrapped: records each
    ``run_many``'s span and when each request's dispatch began."""

    def __init__(self, module, calls: Calls, index_of: dict[int, int], dispatched):
        self.module = module
        self.calls = calls
        self.index_of = index_of
        self.dispatched = dispatched

    def run_many(self, feeds_list):
        s = time.perf_counter()
        for f in feeds_list:
            i = self.index_of.get(id(f))
            if i is not None and np.isnan(self.dispatched[i]):
                self.dispatched[i] = s
        with span("bench.dispatch"):
            out = self.module.run_many(feeds_list)
        self.calls.add(s, time.perf_counter(), len(feeds_list))
        return out


@dataclass
class OpenResult:
    t0: float  # window start (perf_counter seconds)
    due: np.ndarray  # absolute due times
    submitted: np.ndarray
    dispatched: np.ndarray
    done: np.ndarray  # nan where no answer came
    answers: list  # per request: output list, or None
    calls: Calls
    stats: object  # MicroBatcher.stats at the close
    max_batch: int


def drive_open(module, inputs: np.ndarray, offsets: np.ndarray, traffic: dict,
               input_name: str) -> OpenResult:
    """Submit ``inputs[i]`` at ``offsets[i]`` seconds into the window, open
    loop, and wait for every answer (at most ``ANSWER_GRACE_S`` past the
    window)."""
    from repro.serve import MicroBatcher

    n = len(offsets)
    feeds = [{input_name: inputs[i]} for i in range(n)]
    index_of = {id(f): i for i, f in enumerate(feeds)}
    submitted = np.full(n, np.nan)
    dispatched = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    calls = Calls()
    timed = TimedModule(module, calls, index_of, dispatched)
    all_done = threading.Event()
    remaining = [n]
    lock = threading.Lock()

    def finish(i, fut):
        done[i] = time.perf_counter()
        if fut.exception() is None:
            answers[i] = fut.result()
        with lock:
            remaining[0] -= 1
            if remaining[0] == 0:
                all_done.set()

    max_batch = int(traffic["max_batch"])
    mb = MicroBatcher(timed, max_batch=max_batch, max_delay_s=float(traffic["max_delay_s"]))
    t0 = time.perf_counter() + OPEN_LEAD_S
    due = t0 + offsets
    try:
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            submitted[i] = time.perf_counter()
            with span("bench.submit"):
                fut = mb.submit(feeds[i])
            fut.add_done_callback(lambda f, i=i: finish(i, f))
        if n == 0:
            all_done.set()
        all_done.wait(timeout=max(0.0, t0 + offsets[-1] + ANSWER_GRACE_S - time.perf_counter()))
    finally:
        if all_done.is_set():
            mb.close()
    return OpenResult(t0, due, submitted, dispatched, done, answers, calls, mb.stats, max_batch)


@dataclass
class ClosedResult:
    t0: float
    t1: float  # end of the last call
    calls: Calls
    kept: list  # [(call index, outputs)] drawn from the seed
    failed_samples: int


def drive_closed(module, pool: list[list[dict]], seconds: float,
                 rng: np.random.Generator) -> ClosedResult:
    """Back-to-back ``run_many`` over the call inputs in ``pool``, in turn,
    until ``seconds`` have passed; keeps the outputs of ``CLOSED_KEEP``
    calls drawn uniformly from all calls by the seed (reservoir sampling)."""
    calls = Calls()
    kept: list = []
    failed = 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        feeds = pool[i % len(pool)]
        s = time.perf_counter()
        try:
            with span("bench.call"):
                outs = module.run_many(feeds)
        except Exception:  # noqa: BLE001 — a failed call counts as failed samples
            outs = None
            failed += len(feeds)
        e = time.perf_counter()
        calls.add(s, e, len(feeds) if outs is not None else 0)
        if outs is not None:
            if len(kept) < CLOSED_KEEP:
                kept.append((i, outs))
            else:
                j = int(rng.integers(0, i + 1))
                if j < CLOSED_KEEP:
                    kept[j] = (i, outs)
        i += 1
    return ClosedResult(t0, calls.end[-1] if calls.end else t0, calls, kept, failed)
