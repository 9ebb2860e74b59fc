"""Flash attention, forward and backward, per call of the kernel.

Per (batch row, head), with S queries and keys of width D:
forward  = QK^T (2 S S D) + PV (2 S S D)                  = 4 S^2 D
backward = dV, dP, dQ, dK (2 S S D each)                  = 8 S^2 D
(the backward's second QK^T is recomputation: not counted). A causal
mask needs only the lower triangle, half of each.
Bytes are the tensors that must cross HBM once: forward reads Q, K, V
and writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV
(the [S] log-sum-exp rows are left out: under 1 %)."""

from __future__ import annotations


def flash_fwd(batch, heads, seq, head_dim, causal, itemsize=2):
    flops = 4.0 * seq * seq * head_dim * batch * heads
    if causal:
        flops /= 2
    return flops, 4.0 * seq * head_dim * itemsize * batch * heads


def flash_bwd(batch, heads, seq, head_dim, causal, itemsize=2):
    flops = 8.0 * seq * seq * head_dim * batch * heads
    if causal:
        flops /= 2
    return flops, 8.0 * seq * head_dim * itemsize * batch * heads
