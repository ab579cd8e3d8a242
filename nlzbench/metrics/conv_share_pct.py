"""Share of the program's ``compress`` span time spent in its ``conv``
spans (the conventional stage), over the traced window."""
from nlzbench import trace_reduce


def read(run):
    v = run.trace
    if v is None:
        return None
    comp = trace_reduce.clip(v.span_intervals("compress"), v.lo, v.hi)
    conv = trace_reduce.clip(v.span_intervals("conv"), v.lo, v.hi)
    if not comp or not conv:
        return None
    return 100.0 * trace_reduce.length(conv) / trace_reduce.length(comp)
