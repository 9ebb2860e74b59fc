"""Device time of the HLO module ``jit_p2t_kv_scatter_prefill`` (each
prefill's scatter re-lays the whole K and V pools) over the device's
busy time, inside the traced stretch."""

import program_trace

MODULE = "jit_p2t_kv_scatter_prefill"


def read(ctx):
    pt, trace = program_trace.of(ctx), ctx["trace"]
    names = {n for mods in pt.modules.values() for n, _, _ in mods}
    if not any(n.startswith("jit_p2t_") for n in names):
        return None
    busy_s, _ = ctx["reduce"].busy_and_window_s(trace)
    if not busy_s:
        return None
    return 100.0 * program_trace.module_time(pt, trace.window, MODULE) \
        / 1e9 / busy_s
