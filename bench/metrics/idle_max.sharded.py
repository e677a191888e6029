"""The largest share of the traced window in which no operation ran on one
chip, over the chips (``idle.point`` averages them)."""
import kbtrace


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.busy:
        return None
    return max(100.0 * (1.0 - kbtrace.tr.overlap(m, tr.t0, tr.t1)
                        / tr.window_s) for m in tr.busy.values())
