"""Prefill engine steps (synced) per 1000 prefilled tokens."""


def read(run):
    tokens = run.rec.counters.get("prefill_tokens", 0)
    if not tokens:
        return None
    return 1e6 * run.rec.spans.total.get("prefill", 0.0) / tokens
