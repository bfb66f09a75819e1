"""hbm_peak_gib: the device memory the compiler allots the window's step,
in GiB: ``memory_analysis()`` of the executable the window ran, arguments
plus temporaries plus outputs less the outputs aliased to arguments.  It
decides whether the job fits; it is the compiler's bound, read from the
compiled program, not a reading of the device."""


def read(ctx):
    mem = ctx.memory
    return (mem["args"] + mem["temp"] + mem["out"] - mem["alias"]) / 2 ** 30
