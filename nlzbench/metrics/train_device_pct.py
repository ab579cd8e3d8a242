"""Device time of the online-training programs (found by XLA module name)
/ the traced window."""
from nlzbench import trace_reduce
from nlzbench.metrics import _util


def read(run):
    v = run.trace
    if v is None or v.window_s <= 0:
        return None
    iv = v.dev.module_intervals(_util.TRAIN_PROGRAMS, v.lo, v.hi)
    if not iv:
        return None
    return 100.0 * trace_reduce.length(iv) * 1e-9 / v.window_s
