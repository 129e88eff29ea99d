"""The served tokens' FLOPs in the window (flops.py: every linear layer,
attention over the real context, logits where a token is sampled) over
the window times the chip's bf16 peak, in %."""


def read(run):
    f = run.rec.counters.get("flops", 0.0)
    if not f:
        return None
    return 100.0 * f / (run.end_s * run.rec.peaks["bf16_flops_per_s"])
