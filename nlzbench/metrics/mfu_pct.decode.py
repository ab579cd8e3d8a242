"""The skipping DNN's inference FLOPs over every slice of each completed
decode request / (window x peak)."""
from nlzbench.metrics import _util


def read(run):
    if _util.op_kind(run) != "decode" or run.peaks is None \
            or not run.done or run.window_s <= 0:
        return None
    cfg = run.cell.config
    fl = sum(_util.inference_flops(cfg, r.info["field"]) for r in run.done)
    return 100.0 * fl / (run.window_s * run.peaks["flops"])
