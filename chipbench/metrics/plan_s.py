"""plan_s: host seconds of ``repro.launch.steps.segment_plan`` (the DP
planner, ``core.dp``) in set-up; nothing to read where no plan is made."""


def read(ctx):
    return ctx.plan_s
