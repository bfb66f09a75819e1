"""plan_mem_ratio: the compiled step's temporary bytes
(``memory_analysis``) over the activation peak the DP plan budgeted; how far
the plan under-counts what the compiler allocates."""


def read(ctx):
    if not ctx.plan_peak_bytes:
        return None
    return ctx.memory["temp"] / ctx.plan_peak_bytes
