"""TPU kernels: Pallas flash attention, fused elementwise/optimizer
steps, low-precision matmul paths, and the XLA reference attention.

Submodules (imported lazily by their call sites — importing this
package stays cheap):

* ``attention`` — `scaled_dot_product_attention` + remat policies.
* ``pallas_flash`` — the tiled online-softmax flash kernel.
* ``pallas_fused`` — fused AdamW/momentum STEP kernels (bitwise eager
  twins, in-place aliased), rmsnorm, rope.
* ``pallas_matmul`` — int8/int4 weight-only and int8xint8 matmul
  kernels with analytic error bounds (ISSUE 10).
* ``fused_ce`` — chunked fused head + cross-entropy.
"""
