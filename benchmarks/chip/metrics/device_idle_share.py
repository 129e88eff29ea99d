"""Share of the traced window in which no operation ran on the device:
1 - the union of the device's op intervals over the window, in %."""
import reduce


def read(run):
    rec = run.rec
    if rec.trace is None or rec.trace_window is None:
        return None
    busy = reduce.device_busy(rec.trace, rec.trace_window)
    if busy is None or busy["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
