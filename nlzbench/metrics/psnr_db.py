"""Mean PSNR over every (snapshot, field) the window compressed, each
decoded from the first archive file the window wrote for that snapshot."""
import math


def read(run):
    ok = {r.index for r in run.done}
    vals = [a["psnr_db"] for a in run.answers
            if a["op"] in ok and "psnr_db" in a]
    if not vals or not all(math.isfinite(v) for v in vals):
        return None
    return sum(vals) / len(vals)
