"""Device-idle time inside the engine's ``enhance`` spans (encoder-side
enhancement, regulation and the outlier mask) / the traced window."""
from nlzbench.metrics import _phase


def read(run):
    return _phase.idle_in_spans_pct(run, ("enhance",))
