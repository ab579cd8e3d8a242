"""Least time of the interpolation quantizer for every field value the
window compressed / device busy time inside the program's ``conv`` spans.
The least time is max(FLOPs / peak, bytes / HBM bandwidth) with the bytes
the algorithm must read and write (``flops.interp_quantizer_least``)."""
from nlzbench import flops, trace_reduce
from nlzbench.harness import log


def read(run):
    v = run.trace
    if v is None or run.peaks is None:
        return None
    conv = trace_reduce.clip(v.span_intervals("conv"), v.lo, v.hi)
    busy = [trace_reduce.length(trace_reduce.intersect(iv, conv))
            for iv in v.dev.busy(v.lo, v.hi).values()]
    busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
    points = sum(r.values for r in run.ops)
    if busy_s <= 0 or points <= 0:
        return None
    fl, by = flops.interp_quantizer_least(points)
    t, bound = flops.least_time(fl, by, run.peaks)
    log(f"conv_roofline: {bound} bound, least {t:.6f}s, device busy in "
        f"conv spans {busy_s:.6f}s")
    return 100.0 * t / busy_s
