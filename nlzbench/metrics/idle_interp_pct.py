"""Device-idle time inside the conventional stage's ``interp`` spans (the
eager interpolation phases and their per-phase host reads) / the traced
window."""
from nlzbench.metrics import _phase


def read(run):
    return _phase.idle_in_spans_pct(run, ("interp",))
