"""Model FLOP/s utilisation: operations forward + backward REQUIRE per
token (``roofline/model_ops.py``, by the configuration's
``ops_per_token``) x tokens per second of the traced stretch, over
chips x the published bf16 peak."""

from roofline import model_ops


def read(ctx):
    cell = ctx["cell"]
    if not cell.get("peaks") or "tokens_per_s" not in ctx:
        return None
    cfg = cell["config"]
    ops = getattr(model_ops, cfg["ops_per_token"])(cfg, cell["traffic"]["seq"])
    peak = cell["chips"] * cell["peaks"]["bf16_flops_per_s"]
    return 100.0 * ops * ctx["tokens_per_s"] / peak
