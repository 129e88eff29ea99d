"""Hit-KV install (read from the store, layerwise gather, write into the
request's state; synced) per 1000 hit tokens installed."""


def read(run):
    tokens = run.rec.counters.get("hit_tokens", 0)
    if not tokens:
        return None
    return 1e6 * run.rec.spans.total.get("install", 0.0) / tokens
