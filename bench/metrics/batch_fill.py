"""Serving: mean requests per dispatch over ``max_batch``, from
``MicroBatcher.stats``, in percent.  Open-loop cells only.  Moves
``latency_p50_ms``."""


def read(run):
    if run.batches is None or run.batches[1] == 0:
        return None
    requests, dispatches, max_batch = run.batches
    return 100.0 * requests / dispatches / max_batch
