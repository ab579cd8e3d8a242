"""1 - union of device op intervals / traced window, in the decode cell."""
from nlzbench.metrics import _util


def read(run):
    return _util.idle_pct(run) if _util.op_kind(run) == "decode" else None
