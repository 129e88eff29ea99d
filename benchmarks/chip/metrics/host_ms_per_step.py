"""Host time outside the engines per decode step: the window less the
install, prefill, decode (persist included) and store-write spans and
the clock's idle sleeps, over the decode steps."""


def read(run):
    steps = run.rec.counters.get("decode_steps", 0)
    if not steps:
        return None
    t = run.rec.spans.total
    busy = sum(t.get(k, 0.0) for k in ("install", "prefill", "decode",
                                        "write_block", "idle_sleep"))
    return 1e3 * (run.end_s - busy) / steps
