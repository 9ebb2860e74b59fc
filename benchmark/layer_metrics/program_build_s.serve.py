"""Seconds the program spent building its programs (trace, lower,
compile or cache read, cost analysis) in this process: the sum of
``total_s`` over ``paddle2_tpu.profiler.builds()``."""

import program_trace


def read(ctx):
    return program_trace.program_build_s()
