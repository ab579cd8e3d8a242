"""8 x archive bytes / values compressed, per snapshot over the window's
completed ops, then the mean over the snapshots (each snapshot weighs the
same however many times the window compressed it)."""


def read(run):
    per = {}
    for r in run.done:
        if r.archive_bytes:
            per.setdefault(r.info["snapshot"], []).append(
                8.0 * r.archive_bytes / r.values)
    if not per:
        return None
    return sum(sum(v) / len(v) for v in per.values()) / len(per)
