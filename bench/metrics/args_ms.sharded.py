"""The engine's host time placing each call's arguments on the device, in
milliseconds per dispatch, from the program's ``kb.engine.args`` spans on
the server's dispatcher thread in the traced window, over its runs
(``kb.run``)."""
import kbtrace


def read(ctx):
    return kbtrace.per_run_ms(ctx, ("kb.engine.args",))
