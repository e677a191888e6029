"""Share of the traced window in which the first chip is idle while the
server's dispatcher does host work: its innermost ``kb.*`` span is open
and is neither ``kb.dispatch.wait`` (queue empty) nor ``kb.engine.wait``
(blocked on the device)."""
import kbtrace

NOT_HOST = ("kb.dispatch.wait", "kb.engine.wait", "no span")


def read(ctx):
    idle = kbtrace.idle_split(ctx)
    if idle is None:
        return None
    host = sum(s for name, s in idle.items() if name not in NOT_HOST)
    return 100.0 * host / ctx.trace.window_s
