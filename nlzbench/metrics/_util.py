"""Arithmetic the metric readers share."""
from __future__ import annotations


def op_kind(run) -> str:
    return run.cell.traffic["op"]


def rate_MBps(run) -> float | None:
    """Field bytes of every completed op over the window, in MB/s."""
    if not run.done or run.window_s <= 0:
        return None
    return sum(r.field_bytes for r in run.done) / 1e6 / run.window_s


def c_in(cfg: dict, name: str) -> int:
    return 1 + len(cfg["cross_field"].get(name, ()))


def inference_flops(cfg: dict, name: str) -> int:
    """The skipping DNN's forward over every slice of one field."""
    from nlzbench import flops
    h, w = cfg["plane"]
    return int(cfg["slices"]) * flops.dnn_forward_flops(
        h, w, c_in(cfg, name), tuple(cfg["widths"]))


def train_flops_bytes(cfg: dict) -> tuple[int, int]:
    """Forward+backward FLOPs and least bytes of one op's online training
    (every field of the snapshot)."""
    from nlzbench import flops
    h, w = cfg["plane"]
    n = flops.trained_samples(int(cfg["slices"]), int(cfg["epochs"]),
                              int(cfg["batch"]))
    fl = by = 0
    for name in cfg["fields"]:
        c = c_in(cfg, name)
        fl += n * flops.dnn_train_flops(h, w, c, tuple(cfg["widths"]))
        by += n * flops.dnn_train_bytes(h, w, c)
    return fl, by


def idle_pct(run) -> float | None:
    v = run.trace
    if v is None or v.window_s <= 0 or not v.dev.devices:
        return None
    return 100.0 * (1.0 - v.busy_s / v.window_s)


# The jitted programs of the batched engine's online training: the fused
# per-group scan and the per-epoch stacked (vmap) step.
TRAIN_PROGRAMS = ("_train_group_fused", "_epoch_vmapped")

# The jitted program of a single-field decode's inference
# (``online_trainer.predict_residual`` under ``Archive.decode``).
INFER_PROGRAMS = ("predict_graph",)
