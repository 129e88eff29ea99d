"""Persist per finished round: serialising the new blocks and the store
writes they submit."""


def read(run):
    n = run.rec.counters.get("persisted_rounds", 0)
    if not n:
        return None
    t = run.rec.spans.total
    return 1e3 * (t.get("persist", 0.0) + t.get("write_block", 0.0)) / n
