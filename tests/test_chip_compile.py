"""Every Pallas kernel, compiled for a described TPU v5e chip.

The CPU tests interpret the kernels; only the chip's compiler can
refuse a block shape the DMA cannot address, a store at an unaligned
lane offset or a scratch past the scoped-VMEM limit. That compiler is
installed here and compiles for a chip that is described, not attached
(``topologies.get_topology_desc``), with ``interpret=False`` passed
explicitly, at the widths the main paths run. A compile that passes is
not a chip run — ``chip_smoke.py`` is — but it guards every later PR at
no chip time.

ONE file, and the topology is described inside a module-scoped fixture:
only one process at a time may load the TPU library, so it must not be
touched at import, in a ``skipif``/``parametrize`` argument or in
``conftest.py`` (every xdist worker imports every test file).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle2_tpu.kernels import pallas_flash, pallas_fused, pallas_matmul
from paddle2_tpu.serving import paged_attention as pa

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable can be written to the persistent
    # cache but not read back without a chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *avals, kernels=()):
    """Compile ``fn`` for the described chip; the Mosaic kernel must be
    IN the compiled program, under the name its ``pallas_call`` gives
    it (``kernels``: what a device trace will call the custom calls),
    never under the name of an enclosing lambda."""
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in avals]
    compiled = jax.jit(fn).lower(*avals).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "_lambda_" not in text
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    for name in kernels:
        assert any(re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", ln)
                   for ln in calls), (name, [ln[:60] for ln in calls])
    return compiled


def _with_weights(params, fn):
    """``fn`` with the layers' parameters handed in as arguments."""
    def run(weights, *args):
        kept = [p._data for p in params]
        for p, w in zip(params, weights):
            p._data = w
        try:
            return fn(*args)
        finally:
            for p, w in zip(params, kept):
                p._data = w
    return run


def _big_ops(text, seq, least):
    """(name, op, scope path) of the ENTRY's arrays that have ``seq``
    among their dims and ``least`` elements or more, parameters and
    bitcasts apart."""
    entry = text[text.index("\nENTRY "):]
    big = []
    for ln in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]+)\]\S* "
                     r"([\w-]+)\(", ln)
        if m and m.group(3) not in ("parameter", "bitcast",
                                    "get-tuple-element"):
            dims = [int(d) for d in m.group(2).split(",")]
            if seq in dims and math.prod(dims) >= least:
                path = re.search(r'op_name="([^"]*)"', ln)
                big.append((m.group(1), m.group(3),
                            path.group(1) if path else ""))
    return big


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("seq", [1024, 4096])
def test_flash_fwd_bwd(one_chip, seq):
    """The trainer's attention: [8, S, 16, 64] bf16 causal, forward and
    backward (at S 4096 the backward's eight resident tensors and its
    strips pass the walk's VMEM reckoning: the grid kernels)."""
    qkv = ((8 if seq == 1024 else 2, seq, 16, 64), BF16)

    def loss(q, k, v):
        o = pallas_flash.flash_attention_bshd(q, k, v, causal=True,
                                              interpret=False)
        return o.astype(F32).sum()

    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
             kernels=(["flash_fwd", "flash_bwd"] if seq == 1024 else
                      ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"]))


def test_flash_training_calls_are_what_the_benchmark_reads(one_chip):
    """The trainer's attention at the training cell's shape, as the
    benchmark's reader finds it (`flash_roofline_pct.train`, by the
    signature patterns of `gpt2m-pretrain-1k.json`): ONE `flash_fwd`
    custom call a layer with result (bf16 o, f32 lse), ONE `flash_bwd`
    with three bf16 results, and neither moving a per-row statistic 128
    lanes wide (`f32[8,16,1024,128]`: lse and delta at 67 MB a call)."""
    import json
    import pathlib
    cell = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                       / "workloads" / "gpt2m-pretrain-1k.json").read_text())
    qkv = ((8, 1024, 16, 64), BF16)

    def loss(q, k, v):
        o = pallas_flash.flash_attention_bshd(q, k, v, causal=True,
                                              interpret=False)
        return o.astype(F32).sum()

    text = _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv,
                    qkv, kernels=["flash_fwd", "flash_bwd"]).as_text()
    lines = [re.sub(r"^ROOT ", "", ln.strip()) for ln in text.splitlines()]
    calls = [ln for ln in lines if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    assert len(calls) == 2, [ln[:80] for ln in calls]
    for key in ("flash_fwd", "flash_bwd"):
        hits = [ln for ln in calls
                if re.search(cell["kernels"][key]["pattern"], ln)]
        assert len(hits) == 1 and hits[0].startswith(f"%{key}"), (
            key, [ln[:120] for ln in calls])
    # a call's operands are named, not typed, on its line: look the
    # whole program over for a per-row statistic 128 lanes wide
    assert not re.search(r"f32\[8,16,1024,128\]", text)
    assert re.search(r"f32\[8,16,1,1024\]", text)       # lse, one row a head


def test_flash_varlen_packed_fwd_bwd(one_chip):
    """The varlen dispatch (nn.functional.flash_attn_unpadded on TPU)."""
    T, H, D = 2048, 16, 64
    seg = ((T,), jnp.int32)

    def loss(q, k, v, sq, oq, sk, ok):
        o = pallas_flash.flash_attention_varlen_packed(
            q, k, v, sq, oq, sk, ok, interpret=False)
        return o.astype(F32).sum()

    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
             ((T, H, D), BF16), ((T, H, D), BF16), ((T, H, D), BF16),
             seg, seg, seg, seg,
             kernels=["flash_fwd_varlen", "flash_bwd_dkv_varlen",
                      "flash_bwd_dq_varlen"])


def test_flash_inside_a_partitioned_program(topo, monkeypatch):
    """dp2 x mp2 over the four described chips: the partitioner cannot
    split a Mosaic kernel, so the public attention op runs it per shard
    — the kernel in the compiled program sees its LOCAL batch (8/dp)
    and heads (16/mp). The dispatch asks JAX for the platform, which is
    the CPU here: the test steers that one query to the chip's branch."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle2_tpu.distributed import mesh as mesh_mod
    from paddle2_tpu.framework.tensor import Tensor
    from paddle2_tpu.kernels import _platform
    from paddle2_tpu.kernels.attention import scaled_dot_product_attention
    monkeypatch.setattr(_platform, "device_platform", lambda: "tpu")
    # the mesh hybrid_mesh(dp=2, tp=2) builds (what chip_smoke
    # --four-chips trains on): dp x pp x sharding x mp = 2 x 1 x 1 x 2
    from paddle2_tpu.distributed.spec_layout import SpecLayout
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 1, 1, 2),
                tuple(SpecLayout().mesh_axes()))
    prev = mesh_mod.get_mesh(auto_init=False)
    mesh_mod.set_mesh(mesh)
    try:
        qkv = jax.ShapeDtypeStruct(
            (8, 1024, 16, 64), BF16,
            sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

        def loss(q, k, v):
            o = scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True)
            return o._data.astype(F32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            qkv, qkv, qkv).compile().as_text()
    finally:
        mesh_mod.set_mesh(prev)
    kernels = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert kernels
    assert all("bf16[4,8,1024,64]" in ln for ln in kernels)
    assert not any("bf16[8,16,1024,64]" in ln for ln in kernels)


# ---------------------------------------------------------------- paged
def _paged(pps):
    return functools.partial(pa.paged_attention_decode, interpret=False,
                             pages_per_split=pps, layer=1)


def _paged_avals(batch, n_blocks, n_pages, dtype=BF16, heads=16,
                 head_dim=64, bs=16, layers=2):
    pool = ((layers, n_blocks, bs, heads * head_dim), dtype)
    return (((batch, 1, heads, head_dim), dtype), pool, pool,
            ((batch, n_pages), jnp.int32), ((batch,), jnp.int32))


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_paged_decode_single_softmax(one_chip, dtype):
    """What chip_smoke serves: H 16, D 64 (two heads per 128-lane
    page), block 16, a 4096-block pool, 64 pages (1024 tokens), batch
    8."""
    _compile(one_chip, _paged(None),
             *_paged_avals(8, 4096, 64, dtype), kernels=["paged_decode"])


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_paged_decode_serving_cell_shape(one_chip, dtype):
    """What `gpt2m-serve-longdoc-backlog` runs: ONE decode program of 64
    rows x 64 pages over the whole model's 24-layer, 4096-block pool,
    the last layer a static index — the single-softmax body (not
    split-K) with the compute block the code picks, and no copy of
    anything pool-sized around it. bf16 KV is the cell's; float32 KV is
    what an engine over a float32 artifact holds."""
    pool = "[24,4096,16,1024]"
    avals = _paged_avals(64, 4096, 64, dtype, layers=24)
    assert pa.kernel_pages_per_block(64, 16, 16, 64, dtype) == \
        (32 if dtype == BF16 else 16)
    fn = functools.partial(pa.paged_attention_decode, interpret=False,
                           layer=23)
    text = _compile(one_chip, fn, *avals,
                    kernels=["paged_decode"]).as_text()
    assert "paged_decode_split" not in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and pool in ln]


def test_paged_decode_finds_its_runs_once_a_program(one_chip):
    """Which groups of the table are runs of consecutive pages is
    decided from the table alone inside the jitted call: a program of
    several layers over ONE table computes it once (XLA merges the
    identical computations), not once a layer."""
    def layers(q, kp, vp, bt, ln):
        for layer in range(3):
            q = q + pa.paged_attention_decode(q, kp, vp, bt, ln,
                                              interpret=False, layer=layer)
        return q
    assert pa.kernel_pages_per_copy(64, 16, 16, 64, BF16) == 8
    text = _compile(one_chip, layers, *_paged_avals(64, 4096, 64, layers=24),
                    kernels=["paged_decode"]).as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    runs = [ln for ln in text.splitlines()
            if " fusion(" in ln and "= pred[64,8]" in ln]
    assert len(calls) == 3 and len(runs) == 1, (len(calls), runs)


def test_paged_decode_split_k_32k(one_chip):
    """A 32k context (2048 pages) auto-dispatches to split-K."""
    assert not pa.fits_single_softmax(2048, 16, 64, BF16)
    _compile(one_chip, _paged(None), *_paged_avals(8, 20000, 2048))


def test_paged_decode_single_softmax_at_vmem_budget(one_chip):
    """The VMEM accounting is the compiler's, not an assumption: the
    widest context ``fits_single_softmax`` admits compiles as ONE
    softmax, and twice the whole limit is refused."""
    per_page = pa.decode_scratch_vmem_bytes(1, 16, 64, BF16, 16)
    at_budget = pa.VMEM_FIT_BUDGET // per_page
    assert pa.fits_single_softmax(at_budget, 16, 64, BF16, None, 16)
    assert not pa.fits_single_softmax(at_budget + 1, 16, 64, BF16,
                                      None, 16)
    _compile(one_chip, _paged(at_budget),
             *_paged_avals(2, 4096, at_budget))
    over = 2 * pa.VMEM_BYTES // per_page
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(one_chip, _paged(over), *_paged_avals(2, 8192, over))


def test_paged_decode_grouped_query_cell_shape(one_chip):
    """What `lfm2moe-serve-doc3k-backlog` runs: 32 query heads over 8
    key/value heads of 64 (512-lane pages, a [32, 512] query tile), ONE
    decode program of 64 rows x 256 pages (4096 positions) over a
    16,384-block pool of the model's one attention layer. The
    accounting — 256 x (32 rows x 16 slots x 4 B scores + 16 x 512 x 2 B
    of V) = 4.5 MiB of the 8 MiB budget — keeps it on the single-softmax
    body, and the chip's compiler takes it inside the scoped limit."""
    assert pa.decode_scratch_vmem_bytes(256, 16, 64, BF16, 32, 8) == \
        256 * (32 * 16 * 4 + 16 * 512 * 2)
    assert pa.fits_single_softmax(256, 16, 64, BF16, None, 32, 8)
    assert pa.kernel_pages_per_block(256, 16, 32, 64, BF16,
                                     num_kv_heads=8) == 64
    assert pa.kernel_pages_per_copy(256, 16, 32, 64, BF16, None, 8,
                                    16384) == 16
    pool = ((1, 16384, 16, 8 * 64), BF16)
    avals = (((64, 1, 32, 64), BF16), pool, pool,
             ((64, 256), jnp.int32), ((64,), jnp.int32))
    fn = functools.partial(pa.paged_attention_decode, interpret=False,
                           layer=0)
    text = _compile(one_chip, fn, *avals, kernels=["paged_decode"]).as_text()
    assert "paged_decode_split" not in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[1,16384,16,512]" in ln]


def test_paged_decode_group_of_five_cell_shape(one_chip):
    """What `falconh1-serve-gen1k-backlog` runs: 20 query heads over 4
    key/value heads of 128 — a group of 5, no power of two: the query
    tile pads 20 rows to 24 — ONE decode program of 128 rows x 160 pages
    (2,560 positions) over a 20,480-block pool, layer 3 of 4. 160 x (24
    rows x 16 slots x 4 B of scores + 16 x 512 x 2 B of V) = 2.7 MiB of
    the 8 MiB budget keeps it on the single-softmax body."""
    assert pa.fits_single_softmax(160, 16, 128, BF16, None, 20, 4)
    assert pa.kernel_pages_per_block(160, 16, 20, 128, BF16,
                                     num_kv_heads=4) > 1
    assert pa.kernel_pages_per_copy(160, 16, 20, 128, BF16, None, 4,
                                    20480) == 16
    pool = ((4, 20480, 16, 4 * 128), BF16)
    avals = (((128, 1, 20, 128), BF16), pool, pool,
             ((128, 160), jnp.int32), ((128,), jnp.int32))
    fn = functools.partial(pa.paged_attention_decode, interpret=False,
                           layer=3)
    text = _compile(one_chip, fn, *avals, kernels=["paged_decode"]).as_text()
    assert "paged_decode_split" not in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[4,20480,16,512]" in ln]


def _state_step_fits_the_default_vmem(nh, G, P, N, calls):
    """The call asks for no scoped-VMEM limit of its own, and what the
    compiler gave it — the state block in and out, twice each — is what
    the plan reckons (within 2 %) and well inside the 16 MiB a kernel
    gets without asking. (A 4 MB block compiled and ran without a limit
    too: PERF.md section 6, PR 42 — there is no refusal to pin.)"""
    from paddle2_tpu.kernels import ssd
    reckoned = ssd.state_step_vmem_bytes(nh, G, P, N)
    for ln in calls:
        assert '"scoped_memory_configs":[]' in ln
        used = [int(n) for n in re.findall(
            r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', ln)]
        assert used and 4 * ssd.STATE_BLOCK_BYTES <= max(used) \
            <= 1.02 * reckoned
    assert reckoned < (16 << 20) * 0.55


def test_ssm_state_step_cell_shape(one_chip):
    """What `falconh1-serve-gen1k-backlog` runs: 128 rows against a
    float32 pool of 4 layers x 129 slots x [32, 128, 256] (2.16 GB),
    the slot ids scalar-prefetched, two layers' steps in one program.
    The pool is updated where it lies: aliased through both calls (the
    compiled program's aliased bytes are the pool's) and never copied."""
    from paddle2_tpu.kernels import ssd
    pool_shape = (4, 129, 32, 128, 256)

    def two_layers(pool, slots, x, B, C, dt, A, D):
        for layer in (0, 3):
            pool, y = ssd.ssm_state_step(pool, layer, slots, x, B, C, dt,
                                         A, D, interpret=False)
        return pool, y

    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (pool_shape, F32), ((128,), jnp.int32), ((128, 32, 128), BF16),
        ((128, 2, 256), BF16), ((128, 2, 256), BF16), ((128, 32), F32),
        ((32,), F32), ((32,), F32))]
    compiled = jax.jit(two_layers, donate_argnums=(0,)).lower(
        *avals).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert len(calls) == 2 and all(
        re.match(r"\s*(ROOT )?%ssm_state_step(\.\d+)? = ", ln)
        for ln in calls), [ln[:60] for ln in calls]
    pool_bytes = math.prod(pool_shape) * 4
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert stats.temp_size_in_bytes < pool_bytes // 8
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[4,129,32,128,256]" in ln]
    # the grid is the plan's: one group of 16 heads (2 MB) a grid step,
    # two grid steps a row — y comes back [rows, steps, P, heads]
    assert ssd.state_step_plan(32, 2, 128, 256) == (16, 2)
    assert all("f32[128,2,128,16]" in ln.split(" custom-call(")[0]
               for ln in calls)
    _state_step_fits_the_default_vmem(32, 2, 128, 256, calls)


def test_paged_decode_group_of_sixteen_cell_shape(one_chip):
    """What `nemotron3n-serve-reason2k-backlog` runs: 32 query heads
    over 2 key/value heads of 128 — a group of 16, two 8-row tiles a
    key/value head — ONE decode program of 256 rows x 256 pages (4,096
    positions) over a 65,536-block pool of the model's one attention
    layer, on the single-softmax body."""
    assert pa._lane_group(2, 128, 32) == (1, 16, 128)
    assert pa.fits_single_softmax(256, 16, 128, BF16, None, 32, 2)
    pool = ((1, 65536, 16, 2 * 128), BF16)
    avals = (((256, 1, 32, 128), BF16), pool, pool,
             ((256, 256), jnp.int32), ((256,), jnp.int32))
    fn = functools.partial(pa.paged_attention_decode, interpret=False,
                           layer=0)
    text = _compile(one_chip, fn, *avals, kernels=["paged_decode"]).as_text()
    assert "paged_decode_split" not in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[1,65536,16,256]" in ln]


def test_ssm_state_step_eight_groups_cell_shape(one_chip):
    """What `nemotron3n-serve-reason2k-backlog` runs: 256 rows against a
    float32 pool of 4 state-space layers x 257 slots x [64, 64, 128]
    (2.16 GB), EIGHT groups (8 heads share a B and a C), updated where
    it lies."""
    from paddle2_tpu.kernels import ssd
    pool_shape = (4, 257, 64, 64, 128)

    def step(pool, slots, x, B, C, dt, A, D):
        return ssd.ssm_state_step(pool, 3, slots, x, B, C, dt, A, D,
                                  interpret=False)

    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (pool_shape, F32), ((256,), jnp.int32), ((256, 64, 64), BF16),
        ((256, 8, 128), BF16), ((256, 8, 128), BF16), ((256, 64), F32),
        ((64,), F32), ((64,), F32))]
    compiled = jax.jit(step, donate_argnums=(0,)).lower(*avals).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "ssm_state_step" in ln]
    assert calls
    pool_bytes = math.prod(pool_shape) * 4
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= pool_bytes
    assert stats.temp_size_in_bytes < pool_bytes // 8
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[4,257,64,64,128]" in ln]
    # the grid is the plan's: the whole row, all eight groups (2 MB), a
    # grid step — 256 grid steps a call where one group a step was 2,048
    assert ssd.state_step_plan(64, 8, 64, 128) == (64, 1)
    assert all("f32[256,1,64,64]" in ln.split(" custom-call(")[0]
               for ln in calls)
    _state_step_fits_the_default_vmem(64, 8, 64, 128, calls)


def test_paged_decode_block_of_positions_cell_shape(one_chip):
    """What `sdar-serve-gen512-backlog` runs: a pass carries 4 positions
    of each of 64 sequences, 32 query heads over 4 key/value heads of
    128 (512-lane pages): the positions ride the query tile as 4 x 32
    rows ([128, 512]) over ONE walk of 192 pages (3072 positions) of a
    12,288-block pool, layer 3 of 4. 192 x (128 rows x 16 slots x 4 B of
    scores + 16 x 512 x 2 B of V) = 4.5 MiB of the 8 MiB budget keeps it
    on the single-softmax body."""
    assert pa.decode_scratch_vmem_bytes(192, 16, 128, BF16, 4 * 32, 4) == \
        192 * (128 * 16 * 4 + 16 * 512 * 2)
    assert pa.fits_single_softmax(192, 16, 128, BF16, None, 4 * 32, 4)
    assert pa.kernel_pages_per_block(192, 16, 4 * 32, 128, BF16,
                                     num_kv_heads=4) == 64
    assert pa.kernel_pages_per_copy(192, 16, 4 * 32, 128, BF16, None, 4,
                                    12288) == 16
    pool = ((4, 12288, 16, 4 * 128), BF16)
    avals = (((64, 4, 32, 128), BF16), pool, pool,
             ((64, 192), jnp.int32), ((64,), jnp.int32))
    fn = functools.partial(pa.paged_attention_decode, interpret=False,
                           layer=3)
    text = _compile(one_chip, fn, *avals, kernels=["paged_decode"]).as_text()
    assert "paged_decode_split" not in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[4,12288,16,512]" in ln]


def test_paged_mla_decode_cell_shape(one_chip):
    """What `dsv2-serve-doc5k-backlog` runs: 128 rows, 128 query heads
    against the one latent head of 512 + 64 lanes in rows of 640, 384
    pages (6,144 positions) of a 49,152-block pool, layer 4 of 5: the
    streaming body in compute blocks of 96 pages (1.9 MiB a half of
    the double buffer), a run of consecutive pages fetched 16 pages
    (320 KB) a copy, no copy of the pool. What the body asks of the
    scoped VMEM — the double buffer, the float32 accumulator and
    statistics, the query and output tiles twice (the pipeline's two
    buffers) — stays under a third of the compiler's limit, which
    leaves the [128, 1536] score and probability tiles and the
    compiler's own temporaries their room (PR 34 met that limit three
    times on the chip, never in an interpreted test)."""
    assert pa.mla_row_width(512, 64) == 640
    assert pa.mla_pages_per_block(384, 16, 640, BF16) == 96
    assert pa.mla_pages_per_copy(384, 16, 640, BF16) == 16
    asked = (2 * 96 * 16 * 640 * 2 + 128 * 512 * 4 + 2 * 128 * 128 * 4
             + 2 * 128 * (640 + 512) * 2)
    assert asked <= pa.VMEM_BYTES // 3
    avals = (((128, 128, 512), BF16), ((128, 128, 64), BF16),
             ((5, 49152, 16, 640), BF16),
             ((128, 384), jnp.int32), ((128,), jnp.int32))
    fn = functools.partial(pa.paged_mla_decode, scale=0.1147,
                           interpret=False, layer=4)
    text = _compile(one_chip, fn, *avals,
                    kernels=["paged_mla_decode"]).as_text()
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[5,49152,16," in ln]


def test_paged_mla_decode_module_is_pr_36s(one_chip, monkeypatch):
    """``paged_mla_decode`` starts and waits for its copies through the
    routine ``paged_decode`` shares since ISSUE 43 (``_start_block`` /
    ``_wait_block``): at the DeepSeek cell's shape its Mosaic module,
    printed without location info, is character for character the one
    PR 36 measured — a change to the shared routine that moves it is a
    change to that cell's kernel, and is then made on purpose (print
    the new digest with this test and say so in PERF.md)."""
    import hashlib
    from jax._src.pallas.mosaic import pallas_call_registration as reg
    lower = reg.lowering.lower_jaxpr_to_module
    modules = []

    def spy(*args, **kwargs):
        module = lower(*args, **kwargs)
        modules.append(module.operation.get_asm(enable_debug_info=False))
        return module
    monkeypatch.setattr(reg.lowering, "lower_jaxpr_to_module", spy)
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((128, 128, 512), BF16), ((128, 128, 64), BF16),
        ((5, 49152, 16, 640), BF16), ((128, 384), jnp.int32),
        ((128,), jnp.int32))]
    jax.jit(functools.partial(pa.paged_mla_decode, scale=0.1147,
                              interpret=False, layer=4)).lower(*avals)
    assert len(modules) == 1
    assert hashlib.sha256(modules[0].encode()).hexdigest() == (
        "b7343ee667e961f88fff8e4d4bd79e89fbd2c378ac151a89d0099752c07b29f2")


@pytest.mark.parametrize("entry", ["bshd", "bhsd"])
@pytest.mark.parametrize("seq,grid", [(2048, False), (3072, False),
                                      (5120, True)])
def test_flash_mla_prefill_shapes(one_chip, seq, grid, entry):
    """The latent-attention prefill's expanded attention: 128 heads of
    192 query / key lanes against 128 value lanes over S positions,
    bf16, causal, forward only, through both entries — (batch, seq,
    heads, dim) and the head-major one the model takes; the two shorter
    prompt buckets take the walk, the 5,120-token one the grid kernel."""
    assert (pallas_flash._walks(seq, seq, 192, BF16, 1, 1024, 1024, False,
                                128) is None) == grid
    dims = (1, seq, 128) if entry == "bshd" else (1, 128, seq)
    qk, v = (dims + (192,), BF16), (dims + (128,), BF16)
    fn = functools.partial(getattr(pallas_flash, f"flash_attention_{entry}"),
                           causal=True, scale=0.1147, interpret=False)
    _compile(one_chip, fn, qk, qk, v, kernels=["flash_fwd"])


def test_mla_prefill_attention_moves_no_activation(one_chip, monkeypatch):
    """One latent-attention layer of the published widths over a
    3,072-token prompt, compiled as the prefill program holds it: of the
    arrays of S x 128 heads x 128 lanes or more, each is written by a
    matmul (q, k, v, the output projection), the flash kernel, the rope
    on q's rope lanes or the fill of k's — no copy, no transpose, no
    slice or join of a q-, k-, v- or o-sized array in between (the
    token-major program this replaced fails here on four copies and a
    slice a layer)."""
    from paddle2_tpu.kernels import attention
    from paddle2_tpu.models.deepseek import DeepseekV2Config, LatentAttention
    monkeypatch.setattr(attention, "use_pallas", lambda shape: True)
    monkeypatch.setattr(pallas_flash, "interpret_default", lambda: False)
    monkeypatch.setattr(pallas_flash, "_JIT_CACHE", {})
    S = 3072
    layer = LatentAttention(DeepseekV2Config(dtype="bfloat16"))
    params = list(layer.parameters())

    def prefill_attention(u):
        with jax.named_scope("attn"):
            return u + layer.full(u)[0]

    avals = [jax.ShapeDtypeStruct(p._data.shape, p._data.dtype,
                                  sharding=one_chip) for p in params]
    u = jax.ShapeDtypeStruct((1, S, 5120), BF16, sharding=one_chip)
    text = jax.jit(_with_weights(params, prefill_attention)).lower(
        avals, u).compile().as_text()
    big = _big_ops(text, S, S * 128 * 128)
    assert not [b for b in big if b[1] in ("copy", "transpose", "slice",
                                           "concatenate")], big
    assert all(op == "custom-call" and "flash_fwd" in path
               or op == "fusion" and re.search(
                   r"/(q_lora|rope|expand|out)/", path)
               for _, op, path in big), big
    assert len(big) <= 8, big


@pytest.mark.parametrize("seq", [1024, 2048])
def test_flash_block_causal_prefill_shape(one_chip, seq):
    """The block-diffusion prefill's attention: [1, S, 32, 128] bf16
    under the causal mask of blocks of 4, forward and backward (both
    lengths fit VMEM a head: the walk, one fused backward call)."""
    qkv = ((1, seq, 32, 128), BF16)

    def loss(q, k, v):
        o = pallas_flash.flash_attention_bshd(
            q, k, v, causal=True, causal_block=4, interpret=False)
        return o.astype(F32).sum()

    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
             kernels=["flash_fwd", "flash_bwd"])


# ------------------------------------------------------- grouped matmul
def _gmm_fn(lhs, rhs, sizes, first):
    from paddle2_tpu.kernels import moe_gmm
    return moe_gmm.moe_gmm(lhs, rhs, sizes, first, interpret=False)


@pytest.mark.parametrize("rows,k,n", [
    (256, 2048, 1536), (256, 1536, 2048),       # a 64-row decode step
    (4096, 2048, 1536), (4096, 1536, 2048),     # a 1024-token prefill
    (8192, 2048, 1536), (8192, 1536, 2048),     # a 2048-token prefill
    (12288, 2048, 1536), (12288, 1536, 2048)])  # a 3072-token prefill
def test_moe_gmm_cell_shapes(one_chip, rows, k, n):
    """The dropless expert layer's grouped matmul at LFM2-24B-A2B's
    widths: all 64 experts held (and a 65th group where skipped rows
    are parked), bf16, both projections, decode and every prefill
    bucket's rows — at the tiles the rule picks there (128 rows;
    ``[K, 512]`` weight tiles at decode, ``[2048, 768]`` and ``[1536,
    1024]`` at prefill)."""
    _compile(one_chip, _gmm_fn, ((rows, k), BF16), ((64, k, n), BF16),
             ((65,), jnp.int32), ((), jnp.int32), kernels=["moe_gmm"])


def test_moe_gmm_held_share(one_chip):
    """Eight of the 64 experts held: the chip's share of a deployment
    that divides a layer's experts over eight chips."""
    _compile(one_chip, _gmm_fn, ((2048, 2048), BF16),
             ((8, 2048, 1536), BF16), ((65,), jnp.int32), ((), jnp.int32),
             kernels=["moe_gmm"])


def test_moe_gmm_tall_tile(one_chip):
    """Rows enough to fill a 256-row tile a group (8 experts, 512 rows
    each): the tall tile beside the widest weight tile."""
    _compile(one_chip, _gmm_fn, ((4096, 2048), BF16),
             ((8, 2048, 1536), BF16), ((9,), jnp.int32), ((), jnp.int32),
             kernels=["moe_gmm"])


@pytest.mark.parametrize("rows,k,n", [
    (2048, 2048, 768), (2048, 768, 2048),       # a pass: 64 x 4 rows x 8
    (4096, 2048, 768), (4096, 768, 2048),       # a 512-token prefill
    (8192, 2048, 768), (8192, 768, 2048),       # a 1024-token prefill
    (16384, 2048, 768), (16384, 768, 2048)])    # a 2048-token prefill
def test_moe_gmm_128_experts_cell_shapes(one_chip, rows, k, n):
    """`sdar-serve-gen512-backlog`: 128 experts of width 768, 8 a row
    (128 rows a tile; ``[2048, 768]`` and ``[768, 2048]`` weight tiles:
    one column tile a product)."""
    _compile(one_chip, _gmm_fn, ((rows, k), BF16), ((128, k, n), BF16),
             ((129,), jnp.int32), ((), jnp.int32), kernels=["moe_gmm"])


@pytest.mark.parametrize("rows,k,n", [
    (768, 5120, 1536), (768, 1536, 5120),       # a step: 128 rows x 6
    (12288, 5120, 1536), (12288, 1536, 5120),   # a 2048-token prefill
    (18432, 5120, 1536), (18432, 1536, 5120),   # a 3072-token prefill
    (30720, 5120, 1536), (30720, 1536, 5120)])  # a 5120-token prefill
def test_moe_gmm_held_group_cell_shapes(one_chip, rows, k, n):
    """`dsv2-serve-doc5k-backlog`: 20 experts held of 160 routed over
    (a 161st group parks the rest), 6 a row (128 rows a tile; ``[5120,
    256]`` and ``[1536, 1024]`` weight tiles)."""
    _compile(one_chip, _gmm_fn, ((rows, k), BF16), ((20, k, n), BF16),
             ((161,), jnp.int32), ((), jnp.int32), kernels=["moe_gmm"])


@pytest.mark.parametrize("rows,k,n", [
    (1536, 2688, 1920), (1536, 1920, 2688),     # a step: 256 rows x 6
    (12288, 2688, 1920), (12288, 1920, 2688)])  # a 2048-token prefill
def test_moe_gmm_contiguous_half_cell_shapes(one_chip, rows, k, n):
    """`nemotron3n-serve-reason2k-backlog`: 64 experts held of 128 routed
    over (a 129th group parks the rest), 6 a row; the experts' width of
    1,856 is STORED as 1,920 lanes (128 rows a tile; ``[2688, 384]`` and
    ``[1920, 384]`` weight tiles). Nothing of the weights' size is
    copied on the way to the kernel."""
    text = _compile(one_chip, _gmm_fn, ((rows, k), BF16), ((64, k, n), BF16),
                    ((129,), jnp.int32), ((), jnp.int32),
                    kernels=["moe_gmm"]).as_text()
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"bf16[64,{k},{n}]" in ln]


def test_moe_gmm_refuses_the_unpadded_width(one_chip):
    """Why the width is stored padded: at the published 1,856 lanes (14.5
    x 128) no multiple of 128 divides the width, the column tile is all
    of it, and two ``[2688, 1856]`` weight tiles overrun the scoped VMEM
    (19.69 MB of 16). (A ragged last column tile compiles, but the chip
    keeps ``bf16[64, 2688, 1856]`` with the 2,688 axis minor and the
    program then copies all 638 MB of the weights before every call:
    PERF.md section 6, PR 41.)"""
    with pytest.raises(Exception, match="vmem"):
        _compile(one_chip, _gmm_fn, ((1536, 2688), BF16),
                 ((64, 2688, 1856), BF16), ((129,), jnp.int32),
                 ((), jnp.int32), kernels=["moe_gmm"])


@pytest.mark.parametrize("rows", [64, 3072])
def test_rmsnorm_and_rope_at_lfm2_shapes(one_chip, rows):
    """The norm and rotary kernels as the LFM2-MoE programs call them:
    a decode step's 64 rows and a 3072-token prefill, hidden 2048 in
    bf16 with a float32 gain; q of 32 heads and k of 8 heads of 64."""
    def norm(x, w):
        return pallas_fused._rmsnorm(x, w, 1e-5, 512, False)

    _compile(one_chip, norm, ((rows, 2048), BF16), ((2048,), F32),
             kernels=["rmsnorm_fwd"])
    for heads in (32, 8):
        def rope(x, c, s):
            return pallas_fused.fused_rope(x, c, s, interpret=False)
        _compile(one_chip, rope, ((1, rows, heads, 64), BF16),
                 ((rows, 64), F32), ((rows, 64), F32), kernels=["rope"])


@pytest.mark.parametrize("rows", [128, 5120])
def test_rmsnorm_at_hidden_5120(one_chip, rows):
    """The pre-norm as the latent-attention family's programs call it: a
    decode step's 128 rows and a 5,120-token prefill of hidden 5120 in
    bf16 (512 rows of that width overran the scoped VMEM: the public
    entry takes fewer rows of a wider model)."""
    def norm(x, w):
        return pallas_fused.fused_rms_norm(x, w, 1e-6, interpret=False)

    _compile(one_chip, norm, ((rows, 5120), BF16), ((5120,), F32),
             kernels=["rmsnorm_fwd"])


# ---------------------------------------------------------------- fused
def test_fused_adamw_step(one_chip):
    """One [1024, 4096] f32 leaf (an MLP weight's master copy)."""
    leaf = ((1024, 4096), F32)

    def step(p, g, m, v, lr, t):
        return pallas_fused.fused_adamw_step(
            p, g, m, v, lr, t, weight_decay=0.01, interpret=False)

    _compile(one_chip, step, leaf, leaf, leaf, leaf, ((), F32),
             ((), jnp.int32), kernels=["fused_adamw_step"])


def test_fused_adamw_multi_precision(one_chip):
    n = ((1024 * 4096,), F32)

    def step(p, g, m, v, master, lr):
        return pallas_fused.fused_adamw(p, g, m, v, master, lr,
                                        interpret=False)

    _compile(one_chip, step, ((1024 * 4096,), BF16), n, n, n, n,
             ((), F32), kernels=["fused_adamw"])


def test_fused_momentum_step(one_chip):
    leaf = ((1024, 4096), F32)

    def step(p, g, v, lr):
        return pallas_fused.fused_momentum_step(
            p, g, v, lr, nesterov=True, weight_decay=1e-4,
            interpret=False)

    _compile(one_chip, step, leaf, leaf, leaf, ((), F32),
             kernels=["fused_momentum"])


def test_fused_rms_norm_fwd_bwd(one_chip):
    def loss(x, w):
        return pallas_fused.fused_rms_norm(
            x, w, interpret=False).astype(F32).sum()

    _compile(one_chip, jax.grad(loss, argnums=(0, 1)),
             ((8, 1024, 2048), BF16), ((2048,), BF16),
             kernels=["rmsnorm_fwd", "rmsnorm_bwd"])


def test_fused_rope(one_chip):
    def rope(x, cos, sin):
        return pallas_fused.fused_rope(x, cos, sin, interpret=False)

    _compile(one_chip, rope, ((8, 1024, 16, 128), BF16),
             ((1024, 128), F32), ((1024, 128), F32), kernels=["rope"])


# --------------------------------------------------------------- matmul
HEAD = dict(m=8192, k=1024, n=32768)        # the quantized lm_head


def test_int8_matmul(one_chip):
    """interpret=False through the PUBLIC wrapper is the compiled
    kernel (never the XLA dot it takes off-TPU by default)."""
    def mm(x, w):
        return pallas_matmul.int8_matmul(x, w, interpret=False)

    _compile(one_chip, mm, ((HEAD["m"], HEAD["k"]), jnp.int8),
             ((HEAD["k"], HEAD["n"]), jnp.int8))


def test_int8_weight_only_matmul(one_chip):
    def mm(x, w, s):
        return pallas_matmul.int8_weight_only_matmul(x, w, s,
                                                     interpret=False)

    _compile(one_chip, mm, ((HEAD["m"], HEAD["k"]), BF16),
             ((HEAD["k"], HEAD["n"]), jnp.int8), ((HEAD["n"],), F32))


def test_int4_weight_only_matmul(one_chip):
    def mm(x, w, s):
        return pallas_matmul.int4_weight_only_matmul(x, w, s,
                                                     interpret=False)

    _compile(one_chip, mm, ((HEAD["m"], HEAD["k"]), BF16),
             ((HEAD["k"], HEAD["n"] // 2), jnp.uint8),
             ((HEAD["n"],), F32))


def test_explicit_compiled_kernel_never_takes_the_xla_dot():
    """``interpret=False`` is the compiled kernel or an error, never
    the XLA lowering (no topology needed: neither case reaches the
    chip's compiler): operands the blocks do not divide are refused by
    the wrapper, aligned ones by the CPU backend this process runs."""
    x = jnp.zeros((300, 130), jnp.int8)
    w = jnp.zeros((130, 33), jnp.int8)
    with pytest.raises(ValueError, match="interpret=False"):
        pallas_matmul.int8_matmul(x, w, interpret=False)
    with pytest.raises(ValueError, match="interpret=False"):
        pallas_matmul.int8_weight_only_matmul(
            x.astype(F32), w, jnp.ones((33,), F32), interpret=False)
    with pytest.raises(ValueError, match="interpret mode"):
        pallas_matmul.int8_matmul(x[:256], w, interpret=False)
    # the default stays the XLA lowering off-TPU
    assert pallas_matmul.int8_matmul(x, w).shape == (300, 33)


# ---------------------------------- kexaone-serve-mixed8k-backlog's shapes
def test_window_decode_ring_cell_shape(one_chip):
    """`kexaone-serve-mixed8k-backlog`'s sliding layers: 128 rows, 64
    query over 8 key/value heads of 128, the rings of 129 slots x 4
    layers ``[4, 129, 128, 1024]`` seen as 8 pages of 16 a slot — the
    paged single-softmax body under its OWN name (``window_decode``: the
    pattern ``paged_decode`` must read the global layers alone), a whole
    ring one compute block and one copy, and no copy of anything
    ring-sized around it (the view is a bitcast)."""
    from paddle2_tpu.serving.exaone_moe_family import ring_walk
    assert pa._decode_plan(8, 16, 1024, BF16, 129 * 8) == (8, 8)

    def walk(q, ring_k, ring_v, slots, ctx):
        return ring_walk(q, ring_k, ring_v, 3, slots, ctx, False)

    ring = ((4, 129, 128, 1024), BF16)
    text = _compile(one_chip, walk, ((128, 1, 64, 128), BF16), ring, ring,
                    ((128,), jnp.int32), ((128,), jnp.int32),
                    kernels=["window_decode"]).as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert len(calls) == 1 and "%paged_decode" not in calls[0]
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and ("[4,129,128,1024]" in ln or "[4,1032,16,1024]" in ln)]


def test_paged_decode_global_layer_under_the_raised_limit(one_chip):
    """`kexaone-serve-mixed8k-backlog`'s ONE global layer: 128 rows x 576
    pages (9,216 positions) over a 73,728-block pool of 1,024-lane rows.
    Context-resident V for 576 pages (18 MiB) is past the single-softmax
    body's fit budget and within half of the raised limit, which the
    dispatcher then asks for (ROADMAP M11's first repair; through the
    split body a decode step took 101 ms, 88 of them this walk); at the
    compiler's own limit the same call is refused."""
    shape = (576, 16, 64, 128, BF16)
    assert not pa.fits_single_softmax(576, 16, 128, BF16, None, 64, 8)
    assert pa.fits_single_softmax(576, 16, 128, BF16,
                                  pa.VMEM_RAISED_BYTES // 2, 64, 8)
    assert pa.kernel_pages_per_block(*shape, num_kv_heads=8) == 32
    assert pa.kernel_pages_per_copy(*shape, None, 8, 73728) == 8
    pool = ((1, 73728, 16, 1024), BF16)
    avals = (((128, 1, 64, 128), BF16), pool, pool,
             ((128, 576), jnp.int32), ((128,), jnp.int32))
    fn = functools.partial(pa.paged_attention_decode, interpret=False,
                           layer=0)
    text = _compile(one_chip, fn, *avals, kernels=["paged_decode"]).as_text()
    assert "paged_decode_split" not in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[1,73728,16,1024]" in ln]
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(one_chip, functools.partial(fn, pages_per_split=576), *avals)


@pytest.mark.parametrize("cell,shape,kv", [
    ("gpt2m-serve-chat-steady", (64, 16, 16, 64), None),
    ("gpt2m-serve-longdoc-backlog", (64, 16, 16, 64), None),
    ("lfm2moe-serve-doc3k-backlog", (256, 16, 32, 64), 8),
    ("sdar-serve-gen512-backlog", (192, 16, 128, 128), 4),
    ("falconh1-serve-gen1k-backlog", (160, 16, 20, 128), 4),
    ("nemotron3n-serve-reason2k-backlog", (256, 16, 32, 128), 2)])
def test_accepted_cells_keep_the_compilers_own_limit(cell, shape, kv):
    """Every accepted cell's table (pages, block, query heads, head size;
    key/value heads) is within the fit budget: the single-softmax body,
    the compiler's own scoped limit, the program it was."""
    pages, block, heads, head_dim = shape
    assert pa._split_width(*shape, BF16, None, kv) == pages
    assert pa.fits_single_softmax(pages, block, head_dim, BF16, None, heads,
                                  kv)


def test_flash_grid_forward_at_8192(one_chip):
    """The global layer's prefill at the cell's longest prompt: 64 heads
    of 128 over 8,192 positions, causal — past 4,096 the GRID forward,
    which no cell had run since PR 33."""
    qkv = ((1, 8192, 64, 128), BF16)
    fn = functools.partial(pallas_flash.flash_attention_bshd, causal=True,
                           interpret=False)
    _compile(one_chip, fn, qkv, qkv, qkv, kernels=["flash_fwd"])


@pytest.mark.parametrize("rows,k,n", [
    (1024, 6144, 2048), (1024, 2048, 6144),     # a step: 128 rows x 8
    (4096, 6144, 2048), (4096, 2048, 6144),     # a 512-token prefill
    (65536, 6144, 2048), (65536, 2048, 6144)])  # an 8,192-token prefill
def test_moe_gmm_contiguous_eighth_cell_shapes(one_chip, rows, k, n):
    """`kexaone-serve-mixed8k-backlog`: 16 experts held of 128 routed
    over (a 129th group parks the rest), 8 a row, SwiGLU experts of
    width 2,048 under a hidden of 6,144. Nothing of the weights' size is
    copied on the way to the kernel."""
    text = _compile(one_chip, _gmm_fn, ((rows, k), BF16), ((16, k, n), BF16),
                    ((129,), jnp.int32), ((), jnp.int32),
                    kernels=["moe_gmm"]).as_text()
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"bf16[16,{k},{n}]" in ln]


@pytest.mark.parametrize("cell,rows,k,hidden,width,experts,held,router", [
    ("kexaone", 8192, 8, 6144, 2048, 128, 16, {}),
    ("dsv2", 5120, 6, 5120, 1536, 160, 20,
     dict(router="softmax_group_limited", n_group=8, topk_group=3))])
def test_held_prefix_expert_layer_cell_shapes(one_chip, cell, rows, k,
                                              hidden, width, experts, held,
                                              router):
    """The expert layer of `kexaone-serve-mixed8k-backlog`'s 8,192-token
    prefill and of `dsv2-serve-doc5k-backlog`'s 5,120 one, holding an
    eighth of the experts: the loops over the held prefix compile for the
    chip beside the grouped matmuls, the rows behind the prefix are an
    allocation nobody fills, and the program's temporaries are those of
    the formulation it replaced (every assignment gathered, gathered back
    and summed as `[T, k, H]` float32)."""
    from paddle2_tpu.incubate.moe import DroplessExperts
    from test_moe_held_prefix import parent_route_and_run
    # a layer of the cell's counts; its weights arrive as arguments
    layer = DroplessExperts(8, 8, experts, k, held=(0, held), dtype="bfloat16",
                            **router)
    params = list(layer.parameters())

    def aval(p):
        shape = list(p._data.shape)
        if len(shape) == 3:
            shape[1:] = [hidden, width] if p is not layer.w2 \
                else [width, hidden]
        elif len(shape) == 2:
            shape[0] = hidden
        return jax.ShapeDtypeStruct(tuple(shape), BF16, sharding=one_chip)

    def bound(fn):
        def run(weights, a):
            kept = [p._data for p in params]
            for p, w in zip(params, weights):
                p._data = w
            try:
                return fn(a)
            finally:
                for p, w in zip(params, kept):
                    p._data = w
        return run

    avals = [aval(p) for p in params]
    a = jax.ShapeDtypeStruct((rows, hidden), BF16, sharding=one_chip)
    new = jax.jit(bound(lambda x: layer.route_and_run(
        x, interpret=False))).lower(avals, a).compile()
    old = jax.jit(bound(lambda x: parent_route_and_run(
        layer, x, interpret=False))).lower(avals, a).compile()
    text = new.as_text()
    assert text.count("moe_gmm") >= 3 and "AllocateBuffer" in text
    # (the plan's search for the tiles' groups loops in both)
    loop = (r' while\(.*op_name="[^"]*/(dispatch|combine)/'
            r'(?:while/body/closed_call/)?while"')
    # the gather's loop; the sum's over chunks of ranked rows and, in it,
    # the one over a chunk's terms — and no scatter of rows or of counts
    # in either
    assert sorted(m.group(1) for m in re.finditer(loop, text)) \
        == ["combine", "combine", "dispatch"]
    assert not re.search(r"/(dispatch|combine)/[^\"]*scatter", text)
    assert not re.search(loop, old.as_text())
    # both peak at the gathered rows beside the three products' outputs
    # (1.61 GB at 8,192 rows); the loops' chunk buffers add 0.3 %
    assert new.memory_analysis().temp_size_in_bytes \
        <= 1.01 * old.memory_analysis().temp_size_in_bytes


# ------------------------------------------------- the sliding layers' band
@pytest.mark.parametrize("seq", [512, 2048, 8192])
def test_window_band_kernel_cell_shapes(one_chip, seq):
    """`kexaone-serve-mixed8k-backlog`'s three prefill buckets: 64 query
    over 8 key/value heads of 128 lanes side by side, a window of 128,
    q normed and rotated in the step — under the name `window_fwd`, with
    nothing copied or transposed around the call."""
    from paddle2_tpu.kernels import pallas_band

    def band(q, k, v, gain, cos, sin):
        return pallas_band.band_attention(
            q, k, v, 128, 128, q_gain=gain, eps=1e-5, rope=(cos, sin),
            interpret=False)

    text = _compile(one_chip, band, ((1, seq, 8192), BF16),
                    ((1, seq, 1024), BF16), ((1, seq, 1024), BF16),
                    ((128,), BF16), ((seq, 128), F32), ((seq, 128), F32),
                    kernels=["window_fwd"]).as_text()
    assert " copy(" not in text and " transpose(" not in text


def test_sliding_prefill_attention_moves_no_activation(one_chip,
                                                       monkeypatch):
    """One sliding layer of the published widths over a 2,048-token
    prompt, compiled as the prefill program holds it: of the arrays of S
    x 64 heads x 128 lanes or more, each is written by a matmul (q, the
    output projection) or by the band kernel — q reaches the kernel as
    its projection wrote it, and the kernel's output is the output
    projection's operand. The parent's form (q normed and rotated as
    plain XLA, the band as two einsums) holds float32 copies of q in two
    layouts and the scores: 0.35 GB of temporaries at this length, 1.39
    GB at 8,192."""
    from paddle2_tpu.kernels import _platform, pallas_band
    from paddle2_tpu.models.exaone_moe import (ExaoneMoeAttention,
                                               ExaoneMoeConfig)
    monkeypatch.setattr(_platform, "device_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_band, "interpret_default", lambda: False)
    S = 2048
    layer = ExaoneMoeAttention(ExaoneMoeConfig(dtype="bfloat16",
                                               num_hidden_layers=4), 128)
    params = list(layer.parameters())

    def prefill_attention(u):
        with jax.named_scope("attn"), jax.named_scope("window"):
            op, k, v = layer.full(u)
            return u + op, k, v

    avals = [jax.ShapeDtypeStruct(p._data.shape, p._data.dtype,
                                  sharding=one_chip) for p in params]
    u = jax.ShapeDtypeStruct((1, S, 6144), BF16, sharding=one_chip)
    compiled = jax.jit(_with_weights(params, prefill_attention)).lower(
        avals, u).compile()
    big = _big_ops(compiled.as_text(), S, S * 64 * 128)
    assert [op for _, op, _ in big] == ["fusion", "custom-call"], big
    assert "dot_general" in big[0][2] and "window_fwd" in big[1][2], big
    assert compiled.memory_analysis().temp_size_in_bytes < 100 << 20
