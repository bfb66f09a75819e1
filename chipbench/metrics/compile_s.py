"""compile_s: host seconds to lower and compile the Trainer's step for the
window's batch (from the persistent cache when it is warm)."""


def read(ctx):
    return ctx.compile_s
