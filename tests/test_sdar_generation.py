"""SDAR-MoE served: the engine generates what the reference generates, token
for token and pass for pass (moved from ``test_sdar.py``, which states the
tolerances; harness: ``served.py``)."""

import numpy as np
import pytest

from served import (build_as_read, generate_both,  # noqa: F401
                    shared_programs, tiny_engine)
from served import sdar_bench as bench

VOCAB = 503
pytestmark = pytest.mark.usefixtures("shared_programs")


@pytest.mark.parametrize("block,steps", [(4, 4), (4, 2), (4, 1), (8, 2)])
def test_engine_generates_what_the_reference_generates(bench, block, steps):
    """Token for token AND pass for pass (which positions were fixed in
    which pass, at which token), run-ahead on: prompt lengths with every
    remainder mod B, ``max_new_tokens`` ending inside a block."""
    model, cfg, params = build_as_read(bench, 40 + block + steps,
                               block_length=block)
    engine = tiny_engine(model, denoising_steps=steps)
    rng = np.random.default_rng(block * 10 + steps)
    lens = [block * 2 + r for r in range(block)][:4] + [3]
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in lens]
    new = [block + 1, 2 * block, 3, 2 * block + block // 2, 5][:len(lens)]
    # a request of fewer tokens than fill its first block, too
    generate_both(bench, cfg, params, engine, prompts, new, steps)
    assert engine.ahead_steps > 0 and engine.ahead_dropped == 0
    assert engine.allocator.used_count == 0
