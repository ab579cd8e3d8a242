"""The skipping DNN's model FLOPs per compress op (training forward+backward
and inference over every field) x completed ops / (window x peak)."""
from nlzbench.metrics import _util


def read(run):
    if _util.op_kind(run) != "compress" or run.peaks is None \
            or not run.done or run.window_s <= 0:
        return None
    cfg = run.cell.config
    per_op = _util.train_flops_bytes(cfg)[0] + sum(
        _util.inference_flops(cfg, n) for n in cfg["fields"])
    return 100.0 * per_op * len(run.done) / (run.window_s
                                             * run.peaks["flops"])
