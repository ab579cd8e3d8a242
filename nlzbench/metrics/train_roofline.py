"""Least time of the skipping DNN's forward+backward for the samples the
window's ops trained / the training programs' device time."""
from nlzbench import flops, trace_reduce
from nlzbench.harness import log
from nlzbench.metrics import _util


def read(run):
    v = run.trace
    if v is None or run.peaks is None:
        return None
    dev_s = trace_reduce.length(
        v.dev.module_intervals(_util.TRAIN_PROGRAMS, v.lo, v.hi)) * 1e-9
    ops = sum(1 for r in run.ops if r.values)
    if dev_s <= 0 or not ops:
        return None
    fl, by = _util.train_flops_bytes(run.cell.config)
    t, bound = flops.least_time(ops * fl, ops * by, run.peaks)
    log(f"train_roofline: {bound} bound, least {t:.6f}s, training "
        f"programs {dev_s:.6f}s on the device")
    return 100.0 * t / dev_s
