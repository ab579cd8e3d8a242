"""Least time of the skipping DNN's forward over the slices the window's
requests decoded / the device time of the decode's inference program."""
from nlzbench import flops, trace_reduce
from nlzbench.harness import log
from nlzbench.metrics import _util


def read(run):
    v = run.trace
    if v is None or run.peaks is None or _util.op_kind(run) != "decode":
        return None
    dev_s = trace_reduce.length(
        v.dev.module_intervals(_util.INFER_PROGRAMS, v.lo, v.hi)) * 1e-9
    done = [r for r in run.ops if r.values]
    if dev_s <= 0 or not done:
        return None
    cfg = run.cell.config
    h, w = cfg["plane"]
    fl = by = 0
    for r in done:
        c = _util.c_in(cfg, r.info["field"])
        fl += int(cfg["slices"]) * flops.dnn_forward_flops(
            h, w, c, tuple(cfg["widths"]))
        by += int(cfg["slices"]) * flops.dnn_forward_bytes(h, w, c)
    t, bound = flops.least_time(fl, by, run.peaks)
    log(f"infer_roofline: {bound} bound, least {t:.6f}s, inference "
        f"program {dev_s:.6f}s on the device")
    return 100.0 * t / dev_s
