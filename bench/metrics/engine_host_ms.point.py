"""The engine's host time per device dispatch, in milliseconds, from the
program's spans on the server's dispatcher thread in the traced window:
time inside the engine's op methods (``kb.engine.<op>``) less the part
blocked on a result's copy to the host (``kb.engine.wait``), over the
dispatcher's runs (``kb.run``)."""
import kbtrace

OPS = ("kb.engine.lookup", "kb.engine.lazy_grad", "kb.engine.update",
       "kb.engine.flush", "kb.engine.nn_search")


def read(ctx):
    return kbtrace.per_run_ms(ctx, OPS, less=("kb.engine.wait",))
