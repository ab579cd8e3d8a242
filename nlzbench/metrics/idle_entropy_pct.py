"""Device-idle time inside the host coding spans, ``entropy`` (zstd of the
conventional stage's streams) and ``outliers`` (strict-mode outlier
packing), / the traced window."""
from nlzbench.metrics import _phase


def read(run):
    return _phase.idle_in_spans_pct(run, ("entropy", "outliers"))
