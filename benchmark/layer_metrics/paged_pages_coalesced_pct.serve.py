"""Of the live key/value pages of the traced stretch's decode steps,
the share that the paged kernel (``paged_decode``) fetches a run of
consecutive pages at a time (``coalesced_pages`` / ``live_pages`` on
``p2t:decode.dispatch``, summed): the rest — in these cells mostly the
pages a sequence grew one at a time while it decoded — arrives a page a
copy of K and one of V. A program whose spans carry no
``coalesced_pages`` says nothing."""

import program_trace


def read(ctx):
    run = live = 0
    for _, _, _, c in program_trace.spans_named(
            program_trace.of(ctx), "decode.dispatch", ctx["trace"].window):
        if "coalesced_pages" in c and c.get("live_pages"):
            run += c["coalesced_pages"]
            live += c["live_pages"]
    return 100.0 * run / live if live else None
