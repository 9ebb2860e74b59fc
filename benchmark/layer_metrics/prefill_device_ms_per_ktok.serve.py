"""Device time of the ``jit_p2t_prefill`` executions of the traced
stretch, each joined to the ``p2t:prefill.dispatch`` span that enqueued
it (``program_split.launches``), over the sum of their spans' ``tokens``:
ms of device per thousand REAL prompt tokens, so padding shows as cost.
A program whose spans carry no ``launch`` says nothing."""

import program_split


def read(ctx):
    return program_split.prefill_device_ms_per_ktok(ctx)
