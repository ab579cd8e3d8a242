"""Field MB (10^6 B) of every completed decode request / window time."""
from nlzbench.metrics import _util


def read(run):
    return _util.rate_MBps(run) if _util.op_kind(run) == "decode" else None
