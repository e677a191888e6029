"""The server's dispatcher's own host time per device dispatch, in
milliseconds, from the program's spans on the dispatcher thread in the
traced window: forming runs (``kb.dispatch.form``), concatenating their
arguments (``kb.run.args``) and replying (``kb.run.reply``), over its runs
(``kb.run``)."""
import kbtrace

OWN = ("kb.dispatch.form", "kb.run.args", "kb.run.reply")


def read(ctx):
    return kbtrace.per_run_ms(ctx, OWN)
