"""Share of the IVF search's roofline: the least time the window's
``nn_search`` engine calls need (``floors``), over the device busy time
inside ``engine.nn_search`` spans."""
import floors


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.span_device_s("engine.nn_search")
    return 100.0 * floors.nn_floor_s(ctx) / busy if busy > 0 else None
