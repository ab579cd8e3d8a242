"""What the op streams share: the session a configuration describes, the
limits it states, and the checks that decide ``correct``."""
from __future__ import annotations

import math

from nlzbench import quality

# The guarantees the regulation modes state (paper section 3.3): every
# decoded point within the bound (strict, outliers stored), or within twice
# it (relaxed: the regulated head caps the added residual at the bound).
# Unregulated states none.
MODE_LIMIT = {"strict": 1.0, "relaxed": 2.0}


def shape(cfg: dict) -> tuple:
    return (int(cfg["slices"]),) + tuple(int(s) for s in cfg["plane"])


def error_limit(cfg: dict) -> float:
    return MODE_LIMIT[cfg["mode"]]


def gain_limit(cfg: dict) -> float:
    """The least gain in dB over the conventional stage alone that every
    compared op has to show (set from readings, see ``PERF.md``)."""
    return float(cfg["checks"]["enhancer_gain_db"])


def session(cfg: dict, telemetry=None, seed: int = 0):
    """``repro.NeurLZ`` as the configuration states it; ``seed`` seeds the
    enhancer's initial weights and its training order."""
    import repro
    return repro.NeurLZ(
        model=repro.ModelConfig(
            seed=int(seed) % (2 ** 31 - 1),
            widths=tuple(cfg["widths"]), epochs=int(cfg["epochs"]),
            batch=int(cfg["batch"]), lr=float(cfg["lr"]),
            slice_axis=int(cfg["slice_axis"]),
            cross_field={k: tuple(v) for k, v in cfg["cross_field"].items()}),
        engine=repro.EngineConfig(engine=cfg["engine"],
                                  lowering=cfg["lowering"],
                                  telemetry=telemetry),
        regulation=repro.RegulationConfig(mode=cfg["mode"]))


def degraded(arc) -> list:
    """Fields the program left conv-only (its enhancer failed)."""
    bad = set(arc["timing"].get("degraded_fields", []))
    bad |= {n for n, e in arc["fields"].items() if e.get("degraded")}
    return sorted(bad)


def answer(op: int, snapshot: int, name: str, x, y, conv, eb: float) -> dict:
    """One decoded field against its original: the worst error in units of
    the bound, PSNR, and the mean squared error of the decode and of the
    conventional reconstruction alone, both in units of ``eb**2``."""
    ok = y is not None and y.shape == x.shape
    return {
        "op": op, "snapshot": snapshot, "field": name,
        "max_err_over_eb": (quality.max_err_over_eb(x, y, eb) if ok
                            else float("inf")),
        "psnr_db": quality.psnr(x, y) if ok else float("nan"),
        "mse_over_eb2": quality.mse_over_eb2(x, y, eb) if ok
        else float("inf"),
        "conv_mse_over_eb2": quality.mse_over_eb2(x, conv, eb)}


def op_gains(answers: list) -> dict:
    """Per op, the enhancer's gain over the conventional stage alone: the
    mean over the op's fields of each field's gain in dB."""
    per: dict = {}
    for a in answers:
        per.setdefault(a["op"], []).append(
            quality.gain_db(a["conv_mse_over_eb2"], a["mse_over_eb2"]))
    return {op: sum(g) / len(g) for op, g in per.items()}


def checks(answers: list, due: int, cfg: dict) -> dict:
    """The numbers compared, each with its limit: the worst error of any
    answer in units of its bound (at most the limit), the least gain of any
    op over the conventional stage alone (at least the limit: an enhancer
    skipped, untrained or broken reads 0 or below), and the sampled ops
    whose answer never came (an archive that would not decode)."""
    worst = max((a["max_err_over_eb"] for a in answers), default=math.inf)
    least = min(op_gains(answers).values(), default=-math.inf)
    per_op = {a["op"] for a in answers}
    return {
        "max_err_over_eb": {"value": worst, "limit": error_limit(cfg)},
        "enhancer_gain_db": {"value": least, "limit": gain_limit(cfg),
                             "pass_if": ">="},
        "unanswered": {"value": max(0, due - len(per_op)), "limit": 0},
    }


def decode_checks(answers: list, replays: list, due: int, cfg: dict) -> dict:
    """The numbers a decode cell compares, each with its limit: the worst
    error of any answer in units of its bound, the least gain of any answer
    over its conventional reconstruction alone, the worst distance of an
    answer from the reference's replay of its archive entry, and the
    requests that never answered."""
    worst = max((a["max_err_over_eb"] for a in answers), default=math.inf)
    least = min((quality.gain_db(a["conv_mse_over_eb2"], a["mse_over_eb2"])
                 for a in answers), default=-math.inf)
    return {
        "max_err_over_eb": {"value": worst, "limit": error_limit(cfg)},
        "enhancer_gain_db": {"value": least, "limit": gain_limit(cfg),
                             "pass_if": ">="},
        "replay_err_over_eb": {"value": max(replays, default=math.inf),
                               "limit": float(
                                   cfg["checks"]["replay_err_over_eb"])},
        "unanswered": {"value": max(0, due - len(answers)), "limit": 0},
    }
