"""Paged decode attention: one new token per row against its context.

The kernel must read each row's K and V once: ``context x heads x
head_dim`` elements each, per layer. Operations (4 x context x heads x
head_dim per row and layer) are far under the ridge, so the bound is
memory. Query, output and block tables are left out (under 1 % at the
contexts served)."""

from __future__ import annotations


def paged_decode(context_tokens_sum, layers, heads, head_dim, itemsize=2):
    """``context_tokens_sum``: the contexts of all rows of all counted
    decode steps, added up. Returns (flops, bytes)."""
    per_token = heads * head_dim * layers
    return (4.0 * context_tokens_sum * per_token,
            2.0 * context_tokens_sum * per_token * itemsize)
