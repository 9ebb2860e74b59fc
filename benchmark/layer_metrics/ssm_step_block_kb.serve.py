"""The recurrent state one grid step of ``ssm_state_step`` holds, in KB:
the mean ``ssm_block_bytes`` / 1024 over the traced stretch's
``p2t:decode.dispatch`` spans that carry it (the kernel's own plan,
``kernels/ssd.state_step_plan``: as many whole groups of heads as its
byte budget holds; ``ssm_grid_steps`` beside it is the row bucket x the
state-space layers x the grid steps a row). A program whose spans carry
no such count says nothing."""

import program_trace


def read(ctx):
    blocks = [c["ssm_block_bytes"] for _, _, _, c in program_trace.spans_named(
        program_trace.of(ctx), "decode.dispatch", ctx["trace"].window)
        if c.get("ssm_block_bytes")]
    if not blocks:
        return None
    return sum(blocks) / len(blocks) / 1024.0
