"""Device time of one execution of the jitted sampling round (`fused_round`)."""

PROGRAM = "jit_fused_round"


def read(run):
    seconds, calls = run.trace.module_time(PROGRAM)
    return seconds / calls * 1e3 if calls else None
