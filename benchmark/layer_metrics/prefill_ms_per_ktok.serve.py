"""Wall time of the ``admit_and_prefill`` calls that admitted
something, per thousand prompt tokens admitted."""


def read(ctx):
    spans = ctx["spans"]
    tokens = spans.counters.get("prefill_tokens", 0.0)
    if not tokens:
        return None
    return 1e3 * spans.counters["prefill_seconds"] / (tokens / 1e3)
