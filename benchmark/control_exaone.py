#!/usr/bin/env python3
"""``control_kinds.py`` for the K-EXAONE cell (two kinds of attention, a
sliding window, a shared expert beside sigmoid-routed ones): the same
script — ONE engine, the SOUND reading, the reference in a lower
precision, then the float32 reference with one piece bent, each in the
program's place on the same prompts — with this family's pieces.

    python3 benchmark/control_exaone.py --workload <cell> --seed 11 \\
        [--seconds 20] [--precision bfloat16,int8] \\
        [--control window_whole_history,window_127,...] [--sample 4]

Every control must fail by at least one of the cell's limits. The
benchmark's own runs never run this."""

from __future__ import annotations

import sys

import control_kinds


def _zero_shared(leaves: dict) -> dict:
    return {k: v * 0 if k.endswith("_s_down") else v
            for k, v in leaves.items()}


def _rotated(q, k, cfg):
    from reference import exaone_moe
    return exaone_moe.rotated(q, k, cfg)


# what a sound check must NOT pass, as arguments of
# ``serve_routed_kinds.kinds_token_gaps``: a sliding layer given the whole
# history; the window one short; a rotary embedding ADDED to the global
# layer; the rotary embedding dropped from the sliding layers; the q/k
# norm dropped; the routed sum not scaled by 2.5; the selection bias
# ignored; the shared expert's output gone
CONTROLS = {
    "window_whole_history": lambda cfg: {
        "stand_cfg": dict(cfg, sliding_window=cfg[
            "max_position_embeddings"])},
    "window_127": lambda cfg: {
        "stand_cfg": dict(cfg, sliding_window=cfg["sliding_window"] - 1)},
    "rotary_added_to_global": lambda cfg: {
        "patched": {"global_positions": _rotated}},
    "rotary_dropped_from_sliding": lambda cfg: {
        "patched": {"sliding_positions": lambda q, k, cfg: (q, k)}},
    "qk_norm_dropped": lambda cfg: {
        "patched": {"head_norm": lambda x, g, eps: x}},
    "scale_dropped": lambda cfg: {
        "stand_cfg": dict(cfg, routed_scaling_factor=1.0)},
    "selection_bias_ignored": lambda cfg: {
        "patched": {"selection_bias": lambda b: b * 0}},
    "shared_dropped": lambda cfg: {"damage": _zero_shared},
}


if __name__ == "__main__":
    control_kinds.CONTROLS = CONTROLS
    sys.exit(control_kinds.main())
