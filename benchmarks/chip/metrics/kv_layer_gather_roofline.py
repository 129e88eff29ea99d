"""The KV layer gather kernel's share of its roofline in the traced
window: the least time its bytes need at the chip's HBM bandwidth
(each call reads and writes one layer of the hit blocks; memory bound)
over the kernel's device time in the trace, in %."""
import reduce

KERNEL = "kv_layer_gather"


def read(run):
    rec = run.rec
    if rec.trace is None or rec.trace_window is None:
        return None
    secs, n = reduce.op_seconds(rec.trace, rec.trace_window, KERNEL)
    lo, hi = rec.trace_span_perf
    nbytes = sum(b for t, b in rec.gather_calls if lo <= t <= hi)
    if not n or not secs or not nbytes:
        return None
    return 100.0 * nbytes / rec.peaks["hbm_bytes_per_s"] / secs
