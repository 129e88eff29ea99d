"""Decode engine step (synced), less the persists it runs, per step."""


def read(run):
    steps = run.rec.counters.get("decode_steps", 0)
    if not steps:
        return None
    t = run.rec.spans.total
    return 1e3 * (t.get("decode", 0.0) - t.get("persist", 0.0)) / steps
