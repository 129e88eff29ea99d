"""Output tokens emitted within the window, over the window."""
import reduce


def read(run):
    return reduce.tokens_in_window(run.rec.emitted, run.seconds) / run.seconds
