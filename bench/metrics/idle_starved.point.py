"""Share of the traced window in which the first chip is idle while the
server's dispatcher waits on an empty queue (``kb.dispatch.wait``)."""
import kbtrace


def read(ctx):
    idle = kbtrace.idle_split(ctx)
    if idle is None:
        return None
    return 100.0 * idle.get("kb.dispatch.wait", 0.0) / ctx.trace.window_s
