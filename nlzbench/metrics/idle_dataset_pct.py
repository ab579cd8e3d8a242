"""Device-idle time inside the engine's ``dataset`` spans (host f64
``make_dataset``) / the traced window."""
from nlzbench.metrics import _phase


def read(run):
    return _phase.idle_in_spans_pct(run, ("dataset",))
