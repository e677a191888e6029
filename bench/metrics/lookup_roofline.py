"""Share of the point path's roofline: the least time the window's
``lookup`` and ``lazy_grad`` engine calls need (``floors``), over the
device busy time inside ``engine.*`` spans. A write's device work runs
inside the next lookup's span, which waits on it, so the two ops are
counted together."""
import floors


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.span_device_s("engine.")
    return 100.0 * floors.point_floor_s(ctx) / busy if busy > 0 else None
