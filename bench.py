"""Single-chip benchmarks for the BASELINE.json workloads.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

BENCH_MODEL selects the workload (default "gpt" — the driver's headline):
  gpt        GPT-2-medium LM pretraining step (bf16, fused train step)
  ernie      ERNIE-3.0-base SST-2-style fine-tune step (BASELINE config 2)
  resnet50   ResNet-50 ImageNet classification step    (BASELINE config 1)
  scaling    dp weak-scaling step-time ratio THROUGH the framework stack
             (paddle.DataParallel + jit.train_step) on the virtual CPU
             mesh (stand-in for the 8->256 chip probe, config 3/5)
  gpt_hybrid GPT-3-1.3B layer geometry — models.gpt.GPTBlock(
             tensor_parallel=True) under fleet.mp_layers manual_mp —
             through the compiled 1F1B pipeline (pp=4 x mp=2 virtual
             mesh): BASELINE config 4 structure at dryrun scale
  zero3      ERNIE-XL-proxy ZeRO-3 (group_sharded_parallel p_g_os) on
             the virtual 8-device mesh — BASELINE config 5 structure
             at dryrun scale

Baseline semantics (BASELINE.md: "match A100 step time"): vs_baseline is
the ratio of achieved model FLOP/s to an A100 running the same model at
50% MFU (0.5 * 312 bf16 TFLOP/s) — >= 1.0 means this chip matches a
well-tuned A100 on step time. Note the physical ceiling: the sustained
bf16 matmul rate MEASURED on this chip (reported as sustained_matmul_tf)
is ~130-155 TF/s (dispatch-inclusive), so vs_baseline = 1.0 would
require ~100% MFU; the headline number should be read against that
ceiling.
"""

import json
import os
import sys
import time

import numpy as np

A100_AT_HALF_MFU = 0.5 * 312e12

# nominal bf16 dense peak per chip generation (TF/s); used for the MFU
# denominator, keyed on the detected device kind — a kind that is not
# in the table is an error, not a default
_CHIP_PEAKS = {
    "v5 lite": 197e12, "v5e": 197e12, "v5litepod": 197e12,
    "v4": 275e12, "v5p": 459e12,
    "v6 lite": 918e12, "v6e": 918e12, "trillium": 918e12,
}


def _chip_peak():
    """(peak_flops, chip_label) for the device the bench actually runs
    on — a hardcoded v5e constant would mislabel MFU on any other
    generation (ADVICE r3)."""
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in _CHIP_PEAKS.items():
        if key in kind:
            return peak, key
    raise RuntimeError(
        f"device_kind {kind!r} is not in bench._CHIP_PEAKS: add it with "
        "its published peak before reporting an MFU against it")


# the shared lane machinery lives in the bench/ package (ISSUE 17):
# one artifact writer + scratch-dir helper for every lane instead of a
# copy per lane tail
from bench.artifact import (bench_scratch, emit_result, log,
                            write_artifact)


def _require_chip(lane: str) -> None:
    """The device lanes measure a chip: without one they refuse to run
    instead of shrinking to a CPU profile under a device metric's
    name."""
    import jax
    d = jax.devices()[0]
    if d.platform.lower() != "tpu":
        raise SystemExit(
            f"bench.py {lane}: this lane measures a TPU chip and JAX "
            f"reports platform {d.platform!r} — no figure is printed. "
            "Run it on the chip; the virtual-clock lanes (--serving, "
            "--single-chip-speed, ...) are the CPU ones.")


def _sustained_matmul_tf():
    """Measured chained bf16 matmul rate — the honest chip ceiling
    (callers have passed ``_require_chip``)."""
    import jax
    import jax.numpy as jnp
    n = 8192
    a = jnp.asarray(np.random.RandomState(0).randn(n, n) * 0.01,
                    jnp.bfloat16)

    @jax.jit
    def f(x, y):
        return (x @ y) * jnp.bfloat16(1e-2)

    x = f(a, a)
    _ = float(jnp.sum(x.astype(jnp.float32)[:1]))
    t0 = time.perf_counter()
    iters = 40
    for _i in range(iters):
        x = f(x, a)
    _ = float(jnp.sum(x.astype(jnp.float32)[:1]))
    dt = (time.perf_counter() - t0) / iters
    return round(2 * n ** 3 / dt / 1e12, 1)


def _run_steps(one_step, steps, n_warm=3):
    import jax
    t0 = time.time()
    loss = one_step()
    jax.block_until_ready(loss._data)
    log(f"compile+first step: {time.time()-t0:.1f}s  "
        f"loss={float(np.asarray(loss._data)):.3f}")
    for _ in range(n_warm - 1):
        loss = one_step()
    jax.block_until_ready(loss._data)
    t0 = time.time()
    for _ in range(steps):
        loss = one_step()
    jax.block_until_ready(loss._data)
    return (time.time() - t0) / steps, loss


def _batch_cycler(make_batch, n=16):
    """Distinct batches, cycled: a repeated batch converges to a bf16
    fixed point within tens of steps, after which identical inputs +
    identical params make steps degenerate — fresh data keeps every
    step real work."""
    batches = [make_batch(i) for i in range(n)]
    it = [0]

    def next_batch():
        b = batches[it[0] % n]
        it[0] += 1
        return b
    return next_batch


def bench_gpt():
    import jax
    import paddle2_tpu as paddle
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.models import GPTForCausalLM, GPTConfig

    _require_chip("gpt")
    hidden = int(os.environ.get("BENCH_HIDDEN", 1024))
    layers = int(os.environ.get("BENCH_LAYERS", 24))
    heads = hidden // 64
    seq = int(os.environ.get("BENCH_SEQ", 1024))
    batch = int(os.environ.get("BENCH_BATCH", 8))
    vocab = int(os.environ.get("BENCH_VOCAB", 32768))
    steps = int(os.environ.get("BENCH_STEPS", 40))

    # BENCH_REMAT accepts the named granularities plus "search" (the
    # cost-model policy searcher resolves the minimal-recompute policy
    # that fits BENCH_REMAT_BUDGET_GB / the chip HBM)
    remat = os.environ.get("BENCH_REMAT", "dots")
    int8_head = os.environ.get("BENCH_INT8_HEAD", "0") == "1"
    fused_ce = os.environ.get("BENCH_FUSED_CE", "1") == "1"
    budget = os.environ.get("BENCH_REMAT_BUDGET_GB")
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=seq,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_recompute=remat != "none",
                    recompute_granularity=remat if remat != "none" else "full",
                    remat_budget_gb=float(budget) if budget else None,
                    # stacked [L,...] parameter storage: no per-step
                    # restack of the scan operands (r5 framework-tax fix)
                    stacked_blocks=os.environ.get("BENCH_STACKED",
                                                  "1") == "1",
                    # int8 head excludes fused CE (the chunked kernel
                    # owns the head matmul)
                    fused_head_loss=fused_ce and not int8_head,
                    quantized_lm_head=int8_head)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    n_params = model.num_params()
    log(f"params: {n_params/1e6:.1f}M  seq={seq} batch={batch}")
    o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                  multi_precision=True,
                  fused=(True if os.environ.get("BENCH_FUSED_OPT",
                                                "0") == "1" else None))

    def train_fn(ids, labels):
        _, loss = model(ids, labels=labels)
        return loss

    rs = np.random.RandomState(0)
    next_batch = _batch_cycler(lambda i: paddle.to_tensor(
        rs.randint(0, vocab, (batch, seq)).astype(np.int32)))

    if os.environ.get("BENCH_FUSED", "1") == "1":
        fused_step = paddle.jit.train_step(train_fn, o)

        def one_step():
            ids = next_batch()
            return fused_step(ids, ids)
    else:
        st = paddle.jit.to_static(train_fn)

        def one_step():
            ids = next_batch()
            loss = st(ids, ids)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

    dt, loss = _run_steps(one_step, steps)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * n_params + 12 * layers * seq * hidden
    model_flops = tokens_per_sec * flops_per_token
    peak, chip = _chip_peak()
    sustained = _sustained_matmul_tf()
    print(json.dumps({
        "metric": "gpt_lm_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(model_flops / A100_AT_HALF_MFU, 3),
        "step_time_s": round(dt, 4),
        "mfu_vs_chip_peak": round(model_flops / peak, 3),
        # the actionable MFU: against this chip's MEASURED matmul
        # ceiling, not the nominal peak or the A100 bar (which exceeds
        # this chip's physics — see README perf section)
        "mfu_vs_sustained": round(
            model_flops / (sustained * 1e12), 3),
        "chip": chip,
        "sustained_matmul_tf": sustained,
        "model_params_m": round(n_params / 1e6, 1),
        "config": {"hidden": hidden, "layers": layers, "seq": seq,
                   "batch": batch, "vocab": vocab},
        "device": str(jax.devices()[0]),
        "loss": float(np.asarray(loss._data)),
    }))


def bench_ernie():
    """BASELINE config 2: ERNIE-3.0-base SST-2-style fine-tune."""
    import jax
    import paddle2_tpu as paddle
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.models import ErnieForSequenceClassification, \
        ernie3_base

    _require_chip("ernie")
    seq = int(os.environ.get("BENCH_SEQ", 128))
    batch = int(os.environ.get("BENCH_BATCH", 32))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    stacked = os.environ.get("BENCH_STACKED", "1") == "1"
    cfg = ernie3_base(hidden_dropout_prob=0.0,
                      attention_dropout_prob=0.0,
                      stacked_blocks=stacked)
    paddle.seed(0)
    model = ErnieForSequenceClassification(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    n_params = model.num_params()
    log(f"ernie params: {n_params/1e6:.1f}M  seq={seq} batch={batch}")
    o = opt.AdamW(learning_rate=2e-5, parameters=model.parameters(),
                  multi_precision=True)

    def train_fn(ids, labels):
        _, loss = model(ids, labels=labels)
        return loss

    rs = np.random.RandomState(0)

    def mk(i):
        return (paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
            paddle.to_tensor(
                rs.randint(0, cfg.num_classes, (batch,)).astype(np.int32)))
    next_batch = _batch_cycler(mk)
    step = paddle.jit.train_step(train_fn, o)

    def one_step():
        ids, lbl = next_batch()
        return step(ids, lbl)

    dt, loss = _run_steps(one_step, steps)
    tokens_per_sec = batch * seq / dt
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * seq * \
        cfg.hidden_size
    model_flops = tokens_per_sec * flops_per_token
    peak, chip = _chip_peak()
    sustained = _sustained_matmul_tf()
    print(json.dumps({
        "metric": "ernie_sst2_finetune_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(model_flops / A100_AT_HALF_MFU, 3),
        "step_time_s": round(dt, 4),
        "mfu_vs_chip_peak": round(model_flops / peak, 3),
        "mfu_vs_sustained": round(
            model_flops / (sustained * 1e12), 3),
        "sustained_matmul_tf": sustained,
        "chip": chip,
        "model_params_m": round(n_params / 1e6, 1),
        "config": {"seq": seq, "batch": batch,
                   "hidden": cfg.hidden_size, "layers": cfg.num_layers},
        "device": str(jax.devices()[0]),
        "loss": float(np.asarray(loss._data)),
    }))


def bench_resnet50():
    """BASELINE config 1: ResNet-50 ImageNet classification step."""
    import jax
    import paddle2_tpu as paddle
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.vision.models import resnet50

    _require_chip("resnet50")
    batch = int(os.environ.get("BENCH_BATCH", 128))
    steps = int(os.environ.get("BENCH_STEPS", 30))
    size = 224
    paddle.seed(0)
    model = resnet50(num_classes=1000)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    n_params = sum(p.size for p in model.parameters())
    log(f"resnet params: {n_params/1e6:.1f}M  batch={batch}")
    o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=model.parameters(), multi_precision=True)
    import paddle2_tpu.nn.functional as F

    def train_fn(img, labels):
        logits = model(img)
        return F.cross_entropy(logits.astype("float32"), labels)

    rs = np.random.RandomState(0)
    n_cls = 1000

    def mk(i):
        return (paddle.to_tensor(
            (rs.randn(batch, 3, size, size) * 0.5).astype(np.float32))
            .astype("bfloat16"),
            paddle.to_tensor(
                rs.randint(0, n_cls, (batch,)).astype(np.int32)))
    next_batch = _batch_cycler(mk, n=8)
    step = paddle.jit.train_step(train_fn, o)

    def one_step():
        img, lbl = next_batch()
        return step(img, lbl)

    dt, loss = _run_steps(one_step, steps)
    ips = batch / dt
    # fwd FLOPs per image: ResNet-50@224 ~4.1G; the CPU smoke profile
    # runs ResNet-18@64 (~1.8G @224 scaled by the pixel ratio)
    fwd_flops = 4.1e9
    model_flops = ips * 3 * fwd_flops
    peak, chip = _chip_peak()
    sustained = _sustained_matmul_tf()
    print(json.dumps({
        "metric": "resnet50_imagenet_images_per_sec",
        "value": round(ips, 1),
        "unit": "images/s",
        "vs_baseline": round(model_flops / A100_AT_HALF_MFU, 3),
        "step_time_s": round(dt, 4),
        "mfu_vs_chip_peak": round(model_flops / peak, 3),
        "mfu_vs_sustained": round(
            model_flops / (sustained * 1e12), 3),
        "sustained_matmul_tf": sustained,
        "chip": chip,
        "model_params_m": round(n_params / 1e6, 1),
        "config": {"batch": batch, "image": size},
        "device": str(jax.devices()[0]),
        "loss": float(np.asarray(loss._data)),
    }))


def bench_scaling():
    """Weak-scaling probe on the virtual CPU mesh THROUGH THE FRAMEWORK
    STACK (paddle.DataParallel + jit.train_step — round-3 verdict item 2
    replaced the raw-JAX MLP here): per-step time at dp=1 vs dp=N with
    N-fold batch, the efficiency stand-in for BASELINE's 8->256 chip
    target (>=90%). Virtual CPU devices share host cores, so the
    meaningful signal is the COMPILED PROGRAM's partition/collective
    overhead, not wall-clock speedup."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle2_tpu as paddle
    import paddle2_tpu.distributed as dist
    import paddle2_tpu.nn as nn
    import paddle2_tpu.optimizer as opt

    devs = jax.devices()
    N = len(devs)
    rs = np.random.RandomState(0)
    H = 256

    def step_time(n_dev, per_dev_batch=64, iters=20):
        dist.init_mesh({"dp": n_dev}, devices=devs[:n_dev])
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(H, 4 * H), nn.Tanh(),
                            nn.Linear(4 * H, H))
        model = paddle.DataParallel(net)
        o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
        loss_fn = nn.MSELoss()

        def train_fn(x, y):
            return loss_fn(model(x), y)

        step = paddle.jit.train_step(train_fn, o, layers=[model])
        # batches pre-sharded over dp like shard_dataloader does — a
        # replicated batch entering the compiled step costs an in-program
        # reshard (measured 4x step time on the virtual mesh)
        pmesh = dist.ProcessMesh(np.arange(n_dev), dim_names=["dp"])
        xs = [dist.shard_tensor(paddle.to_tensor(
            rs.randn(n_dev * per_dev_batch, H).astype(np.float32)),
            pmesh, [dist.Shard(0)]) for _ in range(4)]
        y = dist.shard_tensor(paddle.to_tensor(
            np.zeros((n_dev * per_dev_batch, H), np.float32)),
            pmesh, [dist.Shard(0)])
        loss = step(xs[0], y)
        jax.block_until_ready(loss._data)
        t0 = time.perf_counter()
        for i in range(iters):
            loss = step(xs[i % 4], y)
        jax.block_until_ready(loss._data)
        return (time.perf_counter() - t0) / iters

    t1 = step_time(1)
    tn = step_time(N)
    # virtual devices TIMESHARE the host cores, so dp=N runs N-fold total
    # work on the same silicon: normalize by N — eff = N*t1/tN isolates
    # the partitioning + collective overhead the compiler added (the
    # quantity that maps to ICI efficiency on real chips)
    eff = N * t1 / tn
    print(json.dumps({
        "metric": "dp_weak_scaling_efficiency",
        "value": round(eff, 3),
        "unit": f"N*t(dp=1)/t(dp={N}), shared-core normalized",
        "vs_baseline": round(eff / 0.9, 3),
        "step_time_1": round(t1 * 1e3, 2),
        f"step_time_{N}": round(tn * 1e3, 2),
        "stack": "paddle.DataParallel + nn + jit.train_step (donated)",
        "note": "virtual CPU mesh timeshares host cores; measures the "
                "compiled program's partition/collective overhead, not "
                "ICI; >1.0 is possible because fixed per-step dispatch "
                "overhead amortizes across the N-fold batch",
    }))


def bench_gpt_hybrid():
    """BASELINE config 4 (GPT-3 1.3B, TP+PP x32) at dryrun scale,
    entirely through the FRAMEWORK's own model code (r4 verdict #3): the
    1.3B layer geometry (hidden 2048, 24 layers, 16 heads) is a stack of
    ``models.gpt.GPTBlock(tensor_parallel=True)`` built from
    ``fleet.mp_layers`` (Column/RowParallelLinear), run under
    ``manual_mp`` inside the compiled 1F1B pipeline
    (``fleet.pipeline_spmd_1f1b``) on a {pp: 4, mp: 2} virtual mesh —
    zero model code outside paddle2_tpu. Sequence/batch scaled so the
    CPU mesh can execute it."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import paddle2_tpu as paddle
    import paddle2_tpu.distributed as dist
    import paddle2_tpu.nn.functional as F
    from paddle2_tpu.distributed.fleet import pipeline_spmd_1f1b
    from paddle2_tpu.distributed.fleet.mp_layers import manual_mp
    from paddle2_tpu.framework import core
    from paddle2_tpu.framework.tensor import Tensor
    from paddle2_tpu.models.gpt import GPTBlock, GPTConfig
    from jax.sharding import NamedSharding, PartitionSpec as P

    S_pp, MP = 4, 2
    mesh = dist.init_mesh({"pp": S_pp, "mp": MP})
    # 1.3B geometry (hidden/layers/heads); seq+batch scaled for dryrun
    H, L, NH = int(os.environ.get("BENCH_HIDDEN", 2048)), 24, 16
    T = int(os.environ.get("BENCH_SEQ", 64))
    B = int(os.environ.get("BENCH_BATCH", 1))
    M = int(os.environ.get("BENCH_MICRO", 4))       # microbatches
    V = 4096
    k = L // S_pp                                    # blocks per stage
    cfg = GPTConfig(vocab_size=V, hidden_size=H, num_layers=L,
                    num_heads=NH, max_position_embeddings=T,
                    tensor_parallel=True, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)
    paddle.seed(0)
    log(f"building {L} GPTBlock(tensor_parallel=True) ...")
    blocks = [GPTBlock(cfg) for _ in range(L)]
    for blk in blocks:
        blk.eval()
    template = blocks[0]
    names = [n for n, _ in template.named_parameters()]
    tparams = [dict(template.named_parameters())[n] for n in names]

    def stacked_spec(p):
        # stage axis over pp, then the param's own GSPMD TP spec
        orig = tuple(p._data.sharding.spec) \
            if hasattr(p._data.sharding, "spec") else ()
        orig = orig + (None,) * (p._data.ndim - len(orig))
        return P("pp", None, *orig)

    specs = [stacked_spec(p) for p in tparams]
    # stacked [S, k, ...] leaves; free the per-block copies as we go
    stacked = []
    for n, spec in zip(names, specs):
        arr = jnp.stack([
            jnp.stack([np.asarray(
                dict(blocks[s * k + j].named_parameters())[n]._data)
                for j in range(k)]) for s in range(S_pp)])
        stacked.append(jax.device_put(arr, NamedSharding(mesh, spec)))
    n_block_params = sum(int(np.prod(a.shape)) for a in stacked)
    for blk in blocks[1:]:
        for _n, p in blk.named_parameters():
            p._replace_data(jnp.zeros((), jnp.float32))   # free memory

    def stage_fn(p_stack, shared, x, sidx):
        orig = [t._data for t in tparams]
        try:
            with core.no_grad(), manual_mp("mp"):
                for j in range(k):
                    for t, leaf in zip(tparams, p_stack):
                        t._data = leaf[j]
                    x = template(Tensor(x))._data
            return x
        finally:
            for t, o in zip(tparams, orig):
                t._data = o

    rs = np.random.RandomState(0)
    head = jnp.asarray(rs.randn(H, V) * 0.05, jnp.float32)
    head_t = Tensor(jax.device_put(head, NamedSharding(mesh, P())))
    x = jnp.asarray(rs.randn(M, B, T, H) * 0.5, jnp.float32)
    labels = jnp.asarray(rs.randint(0, V, (M, B, T)), jnp.int32)
    xr = jax.device_put(x, NamedSharding(mesh, P()))
    lr = jax.device_put(labels, NamedSharding(mesh, P()))

    def loss_fn(y, lbl):
        with core.no_grad():
            logits = F.linear(Tensor(y), head_t)
            ce = F.cross_entropy(logits, Tensor(lbl), reduction="mean")
        return ce._data

    t0 = time.time()
    loss, grads = pipeline_spmd_1f1b(stage_fn, stacked, xr, lr, loss_fn,
                                     param_specs=specs)
    jax.block_until_ready(loss)
    compile_s = time.time() - t0
    iters = int(os.environ.get("BENCH_STEPS", 2))
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, grads = pipeline_spmd_1f1b(stage_fn, stacked, xr, lr,
                                         loss_fn, param_specs=specs)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    n_params = n_block_params + head.size
    bubble = (S_pp - 1) / (M + S_pp - 1)   # 1F1B pipeline bubble
    print(json.dumps({
        "metric": "gpt_hybrid_tp_pp_step_time",
        "value": round(dt * 1e3, 1),
        "unit": "ms/step (virtual 8-dev CPU mesh, pp=4 x mp=2)",
        # no vs_baseline: its file-header meaning (model FLOP/s vs A100)
        # is a chip-throughput claim a virtual CPU mesh cannot make
        "pipeline_utilization": round(1.0 - bubble, 3),
        "pipeline_bubble_fraction": round(bubble, 3),
        "layer_geometry": {"hidden": H, "layers": L, "heads": NH,
                           "seq": T, "batch": B, "micro": M},
        "model_params_m": round(n_params / 1e6, 1),
        "loss": float(np.asarray(loss)),
        "compile_s": round(compile_s, 1),
        "stack": "models.gpt.GPTBlock(tensor_parallel) + fleet.mp_layers"
                 " manual_mp + fleet.pipeline_spmd_1f1b",
        "note": "BASELINE config 4 structure at dryrun scale; ALL model "
                "code lives in paddle2_tpu (r4 verdict #3); CPU "
                "wall-clock is not a chip throughput claim",
    }))


def bench_zero3():
    """BASELINE config 5 (ERNIE-3.0-XL sharding stage-3, 256-chip pod)
    at dryrun scale: ZeRO-3 placement (``p_g_os``) via
    ``distributed.sharding.group_sharded_parallel`` on the virtual
    8-device mesh. Parameters are STORED sharded over the 'sharding'
    axis; the fused train step (jit.train_step + ShardedOptimizer)
    all-gathers them on forward and reduce-scatters grads + sharded
    optimizer states on the update — XLA derives the ZeRO-3 collective
    pattern from the placements. ERNIE-XL layer geometry scaled by
    hidden/layers/seq so the CPU mesh can execute it."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle2_tpu as paddle
    import paddle2_tpu.distributed as dist
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.distributed.sharding import group_sharded_parallel
    from paddle2_tpu.models import ErnieForSequenceClassification
    from paddle2_tpu.models.ernie import ErnieConfig

    N = 8
    dist.init_mesh({"sharding": N})
    # XL-proxy geometry (the real XL is ~3072 hidden x 48 layers);
    # scaled for the virtual mesh, overridable for bigger boxes
    H = int(os.environ.get("BENCH_HIDDEN", 1024))
    L = int(os.environ.get("BENCH_LAYERS", 8))
    T = int(os.environ.get("BENCH_SEQ", 128))
    B = int(os.environ.get("BENCH_BATCH", 8))
    steps = int(os.environ.get("BENCH_STEPS", 4))
    cfg = ErnieConfig(vocab_size=8192, hidden_size=H, num_layers=L,
                      num_heads=H // 64, max_position_embeddings=T,
                      hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    model = ErnieForSequenceClassification(cfg)
    n_params = model.num_params() if hasattr(model, "num_params") else \
        sum(p.size for p in model.parameters())
    o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    model, o, _ = group_sharded_parallel(model, o, level="p_g_os")
    # stage-3 really stores params sharded: count bytes this "device"
    # keeps vs the replicated footprint
    import jax.numpy as jnp  # noqa: F401
    total_bytes = 0
    local_bytes = 0
    sharded_leaves = 0
    for p in model.parameters():
        nbytes = p._data.size * p._data.dtype.itemsize
        total_bytes += nbytes
        spec = getattr(p._data.sharding, "spec", None)
        if spec is not None and "sharding" in str(spec):
            sharded_leaves += 1
            local_bytes += nbytes // N
        else:
            local_bytes += nbytes
    import paddle2_tpu.nn as nn

    def train_fn(ids, labels):
        _, loss = model(ids, labels=labels)
        return loss

    rs = np.random.RandomState(0)

    def mk(i):
        return (paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)),
            paddle.to_tensor(
                rs.randint(0, cfg.num_classes, (B,)).astype(np.int32)))
    next_batch = _batch_cycler(mk, n=4)
    step = paddle.jit.train_step(train_fn, o)

    t0 = time.time()
    ids, lbl = next_batch()
    loss = step(ids, lbl)
    jax.block_until_ready(loss._data)
    compile_s = time.time() - t0
    t0 = time.perf_counter()
    for _ in range(steps):
        ids, lbl = next_batch()
        loss = step(ids, lbl)
    jax.block_until_ready(loss._data)
    dt = (time.perf_counter() - t0) / steps
    print(json.dumps({
        "metric": "zero3_ernie_xl_proxy_step_time",
        "value": round(dt * 1e3, 1),
        "unit": f"ms/step (virtual {N}-dev CPU mesh, sharding={N})",
        # no vs_baseline: a virtual CPU mesh cannot make the chip-
        # throughput claim the file header defines
        "param_memory_fraction_per_device": round(
            local_bytes / total_bytes, 3),
        "sharded_param_leaves": sharded_leaves,
        "model_params_m": round(n_params / 1e6, 1),
        "layer_geometry": {"hidden": H, "layers": L, "seq": T,
                           "batch": B},
        "loss": float(np.asarray(loss._data)),
        "compile_s": round(compile_s, 1),
        "stack": "group_sharded_parallel(p_g_os) + jit.train_step "
                 "(fused donated step)",
        "note": "BASELINE config 5 structure at dryrun scale: params "
                "stored sharded (gather-on-forward, scatter-on-step); "
                "CPU wall-clock is not a chip throughput claim",
    }))


def bench_fault_tolerance():
    """``--inject-fault`` smoke: (a) measures the clean-path overhead of
    ReliableStep — same model stepped bare vs. wrapped, chaos disarmed,
    interleaved A/B trials with medians; REPORT-ONLY, since on a shared
    host run-to-run noise (+-10%) dwarfs the wrapper's real cost (a
    host-memory snapshot every ``snapshot_every`` steps plus reading the
    previous step's already-materialized scalar loss) — and (b) GATES on
    end-to-end recovery when chaos poisons a step AND corrupts a
    checkpoint shard. Prints one JSON line like the other benches;
    CPU-sized so it runs anywhere (the mechanism under test is
    host-side)."""
    import tempfile

    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.nn.functional as F
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.distributed.fault_tolerance import (
        CheckpointManager, ReliableStep, chaos)

    def build():
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 64))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

        def step(x, y):
            loss = F.mse_loss(model(x), y)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        return model, o, step

    rs_data = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)),
                paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)))
               for _ in range(8)]
    steps, warm, trials = 30, 10, 5

    def timed_loop(run_one):
        t0 = time.perf_counter()
        for i in range(steps):
            run_one(*batches[i % len(batches)])
        return (time.perf_counter() - t0) / steps

    # interleaved A/B trials + medians: on a shared/noisy host a single
    # back-to-back pair routinely reads +-10% either way, which would
    # make the "no clean-path overhead" claim a coin flip
    chaos.disarm()
    _, _, bare_step = build()
    model, o, step = build()
    reliable = ReliableStep(model, o, snapshot_every=20)

    def guarded_step(x, y):
        return reliable.run(step, x, y)

    for i in range(warm):
        bare_step(*batches[i % len(batches)])
        guarded_step(*batches[i % len(batches)])
    bare_t, guarded_t = [], []
    for _ in range(trials):
        bare_t.append(timed_loop(bare_step))
        guarded_t.append(timed_loop(guarded_step))
    reliable.finalize()
    bare = float(np.median(bare_t))
    guarded = float(np.median(guarded_t))
    overhead_pct = (guarded - bare) / bare * 100.0

    # chaos leg: poison one step + corrupt one checkpoint shard on write
    with tempfile.TemporaryDirectory() as root:
        model, o, step = build()
        mgr = CheckpointManager(root, keep_last=2)
        rel = ReliableStep(model, o, snapshot_every=1)
        chaos.arm("poison_loss:5,corrupt_shard:2")
        commit_errors = 0
        for i in range(20):
            rel.run(step, *batches[i % len(batches)])
            if (i + 1) % 5 == 0:
                rel.finalize()
                try:
                    mgr.save({"model": model.state_dict()}, i + 1)
                except Exception:
                    commit_errors += 1   # corrupted save: not committed
        rel.finalize()
        fired = [k for k, _ in chaos.fired_log()]
        chaos.disarm()
        state = {"model": build()[0].state_dict()}
        resumed = mgr.restore(state)
        recovered = (rel.stats["retries"] >= 1 and commit_errors == 1
                     and resumed is not None)

    print(json.dumps({
        "metric": "fault_tolerance_smoke",
        "value": round(overhead_pct, 2), "unit": "% clean-path overhead",
        "clean_step_ms": round(bare * 1e3, 3),
        "guarded_step_ms": round(guarded * 1e3, 3),
        "faults_fired": fired, "retries": rel.stats["retries"],
        "uncommitted_corrupt_saves": commit_errors,
        "resumed_from_step": resumed, "recovered": bool(recovered),
    }))
    return 0 if recovered else 1


def bench_guardrails():
    """``--guardrails`` smoke: measures the clean-path cost of the full
    numerical-guardrail stack — GradScaler's fused non-finite sentinel
    (rank-consistent found_inf), FLAGS_check_loss_finite, and a
    ReliableStep wrapper — against a bare fp32 loop, chaos disarmed,
    interleaved A/B trials with medians (REPORT-ONLY, same rationale as
    --inject-fault). GATES on the host-sync invariant: the sentinel
    must read back exactly ONE scalar per step (the skip decision the
    reference AMP path already pays), independent of parameter count —
    never a per-parameter any()/bool() chain."""
    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.nn.functional as F
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.amp import GradScaler
    from paddle2_tpu.distributed.fault_tolerance import (ReliableStep,
                                                         chaos, numerics)

    def build(mode):
        """mode: 'bare' fp32 loop; 'sentinel' adds the loss sentinel
        consumers (ReliableStep deferred check + check_loss_finite) —
        the no-extra-sync claim under test; 'amp' adds GradScaler's
        fused grad sentinel on top (whose ONE readback per step is the
        skip decision AMP inherently pays)."""
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 64))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
        if mode == "amp":
            scaler = GradScaler(init_loss_scaling=2.0 ** 10)

            def inner(x, y):
                loss = F.mse_loss(model(x), y)
                scaler.scale(loss).backward()
                scaler.step(o)
                scaler.update()
                o.clear_grad()
                return loss
        else:
            def inner(x, y):
                loss = F.mse_loss(model(x), y)
                loss.backward()
                o.step()
                o.clear_grad()
                return loss
        if mode == "bare":
            return inner, None
        reliable = ReliableStep(model, o, snapshot_every=20)

        def step(x, y):
            return reliable.run(inner, x, y)
        return step, reliable

    rs_data = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)),
                paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)))
               for _ in range(8)]
    steps, warm, trials = 30, 10, 5

    def timed_loop(run_one):
        t0 = time.perf_counter()
        for i in range(steps):
            run_one(*batches[i % len(batches)])
        return (time.perf_counter() - t0) / steps

    chaos.disarm()
    paddle.set_flags({"FLAGS_check_loss_finite": True})
    bare_step, _ = build("bare")
    sent_step, sent_rel = build("sentinel")
    amp_step, amp_rel = build("amp")
    for i in range(warm):
        bare_step(*batches[i % len(batches)])
        sent_step(*batches[i % len(batches)])
        amp_step(*batches[i % len(batches)])

    def syncs_over(run_one):
        s0 = numerics.host_sync_count()
        for i in range(steps):
            run_one(*batches[i % len(batches)])
        return (numerics.host_sync_count() - s0) / steps

    # host-sync invariants: the loss sentinel adds ZERO readbacks (the
    # loss was already on host); the grad sentinel adds exactly ONE per
    # step (the skip decision), regardless of parameter count
    sent_syncs = syncs_over(sent_step)
    amp_syncs = syncs_over(amp_step)
    bare_t, sent_t, amp_t = [], [], []
    for _ in range(trials):
        bare_t.append(timed_loop(bare_step))
        sent_t.append(timed_loop(sent_step))
        amp_t.append(timed_loop(amp_step))
    sent_rel.finalize()
    amp_rel.finalize()
    paddle.set_flags({"FLAGS_check_loss_finite": False})
    bare = float(np.median(bare_t))
    sent = float(np.median(sent_t))
    amp = float(np.median(amp_t))
    sentinel_overhead_pct = (sent - bare) / bare * 100.0
    ok = (sent_syncs == 0.0 and amp_syncs <= 1.0
          and sent_rel.stats["retries"] == 0
          and amp_rel.stats["retries"] == 0)

    print(json.dumps({
        "metric": "guardrails_smoke",
        "value": round(sentinel_overhead_pct, 2),
        "unit": "% clean-path overhead of the loss sentinel",
        "bare_step_ms": round(bare * 1e3, 3),
        "sentinel_step_ms": round(sent * 1e3, 3),
        "amp_guarded_step_ms": round(amp * 1e3, 3),
        "sentinel_host_syncs_per_step": round(sent_syncs, 3),
        "amp_host_syncs_per_step": round(amp_syncs, 3),
        "spurious_retries": sent_rel.stats["retries"]
        + amp_rel.stats["retries"],
        "stack": "ReliableStep deferred check + check_loss_finite "
                 "(sentinel) | + GradScaler fused rank-consistent "
                 "found_inf (amp)",
        "note": "REPORT-ONLY timing (shared-host noise); GATES on zero "
                "extra loss-sentinel syncs, <=1 amp sync per step, and "
                "zero spurious retries",
        "ok": bool(ok),
    }))
    return 0 if ok else 1


def bench_flight_recorder():
    """``--flight-recorder`` smoke: run the train loop with recording ON
    vs OFF (interleaved A/B trials, medians — shared-host noise
    rationale as --inject-fault) and GATE overhead at < 3% of step
    time. Also gates on the dump pipeline end-to-end: the dump must be
    parseable jsonl whose events cover the loop's steps and whose
    stacks section is non-empty (evidence quality, not just speed)."""
    import tempfile

    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.nn.functional as F
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.distributed.fault_tolerance import (ReliableStep,
                                                         chaos,
                                                         flight_recorder)

    def build():
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 64))
        o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

        def inner(x, y):
            loss = F.mse_loss(model(x), y)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        reliable = ReliableStep(model, o, snapshot_every=50)

        def step(x, y):
            return reliable.run(inner, x, y)

        return step, reliable

    rs_data = np.random.RandomState(0)
    batches = [(paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)),
                paddle.to_tensor(rs_data.randn(32, 64).astype(np.float32)))
               for _ in range(8)]
    steps, warm, trials = 40, 10, 7

    def timed_loop(run_one):
        """Per-STEP wall times: host noise (scheduler burps, shared-box
        contention) only ever ADDS time to a step, so the min over many
        individually-timed steps is the loop's true floor — the only
        statistic that can resolve a sub-1% recording cost at this step
        size."""
        out = []
        for i in range(steps):
            t0 = time.perf_counter()
            run_one(*batches[i % len(batches)])
            out.append(time.perf_counter() - t0)
        return out

    chaos.disarm()
    flight_recorder.disable()
    off_step, off_rel = build()
    with tempfile.TemporaryDirectory() as flight_dir:
        # ONE recorder for every ON leg (the ring accumulates across
        # trials); the process-global hook is suspended for OFF legs.
        # Leg order ALTERNATES per trial so slow host drift cancels out
        # of the paired per-trial overheads instead of reading as cost.
        on_step, on_rel = build()
        fr = flight_recorder.enable(flight_dir, rank=0,
                                    install_hooks=False)
        flight_recorder.suspend()
        for i in range(warm):
            off_step(*batches[i % len(batches)])
            flight_recorder.resume(fr)
            on_step(*batches[i % len(batches)])
            flight_recorder.suspend()
        n0 = fr.events_recorded()
        off_times, on_times = [], []
        for trial in range(trials):
            if trial % 2 == 0:
                off_times += timed_loop(off_step)
                flight_recorder.resume(fr)
                on_times += timed_loop(on_step)
                flight_recorder.suspend()
            else:
                flight_recorder.resume(fr)
                on_times += timed_loop(on_step)
                flight_recorder.suspend()
                off_times += timed_loop(off_step)
        off_rel.finalize()
        flight_recorder.resume(fr)
        on_rel.finalize()
        events_per_step = ((fr.events_recorded() - n0)
                           / max(1, trials * steps))
        # dump BEFORE the microbench floods the ring with bench ticks
        dump = flight_recorder.dump("bench_smoke")
        # per-event cost, microbenched on the same recorder: the gate
        # multiplies it by the instrumented loop's real events/step —
        # deterministic where a wall-clock A/B on a contended host is
        # a ±8% coin flip around a ~0.01% true effect
        t0 = time.perf_counter()
        for i in range(50000):
            fr.record("bench_tick", i=i)
        per_event_s = (time.perf_counter() - t0) / 50000
        flight_recorder.disable()
        lines = [json.loads(ln) for ln in open(dump)]
        kinds = {ln.get("kind") for ln in lines if ln["type"] == "event"}
        dump_ok = (lines[0]["type"] == "header"
                   and "step_begin" in kinds and "step_ok" in kinds
                   and any(ln["type"] == "stacks" and ln["threads"]
                           for ln in lines))

    # floor-vs-floor wall clock (REPORTED, not gated: on a shared host
    # even per-step floors wobble ±8%, swamping the ~0.01% true cost)
    off = float(min(off_times))
    on = float(min(on_times))
    ab_delta_pct = (on - off) / off * 100.0
    # THE GATE: real events/step x real per-event cost vs the step
    # floor — recording must cost < 3% of step time
    overhead_pct = events_per_step * per_event_s / off * 100.0
    ok = overhead_pct < 3.0 and dump_ok and events_per_step >= 1.0 \
        and off_rel.stats["retries"] == 0 and on_rel.stats["retries"] == 0

    print(json.dumps({
        "metric": "flight_recorder_smoke",
        "value": round(overhead_pct, 4),
        "unit": "% step-time overhead of recording (gated)",
        "gate_pct": 3.0,
        "events_per_step": round(events_per_step, 2),
        "per_event_us": round(per_event_s * 1e6, 3),
        "off_step_ms": round(off * 1e3, 3),
        "on_step_ms": round(on * 1e3, 3),
        "ab_delta_pct": round(ab_delta_pct, 2),
        "dump_parseable": bool(dump_ok),
        "stack": "ReliableStep-wrapped loop; ring capacity default; "
                 "interleaved A/B per-step floors (reported) + "
                 "events/step x per-event cost (gated)",
        "note": "ab_delta_pct is REPORT-ONLY (shared-host noise "
                "rationale as --inject-fault); the gate is the "
                "measured recording cost per step",
        "ok": bool(ok),
    }))
    return 0 if ok else 1


def bench_sdc():
    """``--sdc``: the silent-data-corruption defense gate, now a
    registry lane. Drill and stdout JSON line unchanged; see
    ``bench/scenarios/sdc.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("sdc")


def bench_reliable_step():
    """``--reliable-step``: gates the instrumented compiled train step.
    Ported byte-for-byte onto the ``bench/scenarios/`` registry lane.
    Drill and stdout JSON line unchanged (plus the
    ``RELIABLE_STEP_r01.json`` artifact); see
    ``bench/scenarios/reliable_step.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("reliable-step")


def bench_observability():
    """``--observability``: the metrics-plane / cost-model / perf_doctor
    triage gate, ported byte-for-byte onto the ``bench/scenarios``
    registry (ISSUE 20 satellite): drills, gates, and stdout JSON line
    unchanged (the lane now also writes ``OBSERVABILITY_r01.json``);
    see ``bench/scenarios/observability.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("observability")


def bench_elastic():
    """``--elastic``: the node-loss MTTR gate, now a registry lane.
    Drill and stdout JSON line unchanged; see
    ``bench/scenarios/elastic.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("elastic")


def bench_multichip_scaling():
    """Pod-scale hybrid-parallel scaling gate (BASELINE config 4: GPT-3
    1.3B, tp+pp, 32 chips) — cost x rate, ZERO wall-clock A/B.

    Three layers of evidence, all deterministic:

    1. **Bitwise parity** (executed on the 8-virtual-device CPU mesh):
       the comm-efficiency paths must be pure schedule shapes —
       bucketed dp grad reduction == per-leaf reduction, and ZeRO-3
       layer-ahead prefetch == eager gather-all, bit for bit.
    2. **Modeled 32-chip scaling efficiency** (cost x rate): the full
       GPT-1.3B tp=2 x pp=4 geometry's per-chip FLOPs + per-collective
       wire bytes (tp activation all-reduces on ICI, pp microbatch
       p2p, bucketed dp grad reduce on DCN) under the observability
       LinkModel + overlap split. Efficiency 8->32 chips =
       modeled_step(8) / modeled_step(32), gated >= 85%. The same
       model WITHOUT bucketing (one monolithic exposed grad reduce)
       must fail the gate — bucketing+overlap is load-bearing, not
       decorative.
    3. **exposed-comm %** via perf_doctor: the bucketed stream's
       exposed-comm share must DROP vs the unbucketed baseline, read
       back through the same CLI CI uses, so overlap regressions are
       attributable.
    4. **The 256-chip ladder** (BASELINE config 5: ERNIE-3.0-XL-class
       ZeRO-3 across DCN slices, 8 -> 32 -> 64 -> 128 -> 256):
       executed bitwise/1-ulp parities for the four ladder levers
       (hierarchical ICI/DCN collectives, interleaved-VPP v>1 vs v=1,
       DCN-aware bucket sizing, collective-matmul fused vs unfused),
       then the cost x rate ladder itself — modeled 8->256 efficiency
       gated >= 0.90 with the FLAT configuration (flat collectives,
       v=1, monolithic grad reduce, exposed tp gather) required to
       FAIL the same gate and every lever required to be individually
       load-bearing. Composes the reliability plane at scale: a
       modeled 256-chip kill-and-rescale drill (detect -> quarantine
       -> re-form -> buddy fetch -> warm-cache compile -> replay, all
       priced through the cost model) gating recovery cost SUBLINEAR
       in world size. Emits the byte-identical MULTICHIP_256_r01.json
       artifact plus ici/dcn-split perf_doctor streams.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np_
    import paddle2_tpu as paddle
    import paddle2_tpu.nn as nn
    import paddle2_tpu.optimizer as opt
    import paddle2_tpu.distributed as dist
    from paddle2_tpu.distributed.bucket import (BucketPlan, bucketed_pmean,
                                                plan_buckets)
    from paddle2_tpu.distributed.spec_layout import SpecLayout
    from paddle2_tpu.observability.cost_model import (
        DEFAULT_DCN_GBPS, DEFAULT_ICI_GBPS, CollectiveTraffic, LinkModel,
        StepCost)

    gates = {}
    info = {}

    # ---- 1a. bucketed vs per-leaf dp grad reduction: bitwise (traced,
    # shard_map over the hybrid mesh's dp axis — the exact primitive
    # pipeline_spmd_1f1b(grad_bucket_bytes=) dispatches)
    layout = SpecLayout()
    mesh = dist.init_mesh(layout.mesh_axes(dp=2, pp=2, fsdp=1, tp=2))
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    rs = np_.random.RandomState(0)
    # GPT-ish mixed-shape/mixed-dtype grad tree (weights, bias, norm)
    tree = {
        "wqkv": jnp.asarray(rs.randn(64, 192), jnp.float32),
        "wo": jnp.asarray(rs.randn(64, 64), jnp.float32),
        "ffn": [jnp.asarray(rs.randn(64, 256), jnp.float32),
                jnp.asarray(rs.randn(256, 64), jnp.float32)],
        "bias": jnp.asarray(rs.randn(256), jnp.float32),
        "norm": jnp.asarray(rs.randn(64), jnp.bfloat16),
    }

    def per_leaf(t):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "dp"), t)

    def bucketed(t):
        return bucketed_pmean(t, "dp", 4096.0)  # tiny -> many buckets

    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    run_pl = jax.jit(shard_map(per_leaf, mesh=mesh, in_specs=(specs,),
                               out_specs=specs))
    run_bk = jax.jit(shard_map(bucketed, mesh=mesh, in_specs=(specs,),
                               out_specs=specs))
    a = jax.tree_util.tree_leaves(run_pl(tree))
    b = jax.tree_util.tree_leaves(run_bk(tree))
    bucketed_bitwise = all(
        np_.array_equal(np_.asarray(x), np_.asarray(y))
        for x, y in zip(a, b))
    gates["bucketed_grads_bitwise"] = bucketed_bitwise
    # dispatch-count story at the DEFAULT bucket size (parity above ran
    # a tiny limit to force the multi-bucket split path): mixed-dtype
    # leaves coalesce to one bucket per dtype
    n_leaves = len(a)
    n_buckets = len(plan_buckets(
        [(tuple(g.shape), g.dtype)
         for g in jax.tree_util.tree_leaves(tree)], 25e6))
    gates["buckets_coalesce_dispatches"] = n_buckets < n_leaves
    info["bucket_dispatches"] = {"per_leaf": n_leaves,
                                 "bucketed_25mb": n_buckets}
    log(f"bucketed-vs-per-leaf pmean: bitwise={bucketed_bitwise} "
        f"({n_leaves} leaves -> {n_buckets} buckets @ 25MB)")

    # ---- 1b. ZeRO-3 prefetch vs eager gather-all: bitwise through the
    # compiled train step (the schedule the 256-chip config runs)
    def run_zero3(prefetch, depth=1):
        dist.init_mesh({"sharding": 8})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.Tanh(),
                            nn.Linear(32, 8))
        o = opt.Adam(learning_rate=1e-2, parameters=net.parameters())
        _, o, _ = dist.group_sharded_parallel(
            net, o, "p_g_os", prefetch=prefetch, prefetch_depth=depth)
        step = paddle.jit.train_step(
            lambda x, y: ((net(x) - y) ** 2).mean(), o, layers=[net])
        rs2 = np_.random.RandomState(1)
        for _ in range(3):
            step(paddle.to_tensor(rs2.randn(16, 8).astype(np_.float32)),
                 paddle.to_tensor(rs2.randn(16, 8).astype(np_.float32)))
        return [np_.asarray(p._data).copy() for p in net.parameters()]

    w_eager = run_zero3(False)
    w_pref = run_zero3(True, depth=1)
    prefetch_bitwise = all(np_.array_equal(x, y)
                           for x, y in zip(w_eager, w_pref))
    gates["zero3_prefetch_bitwise"] = prefetch_bitwise
    log(f"zero3 prefetch-vs-eager: bitwise={prefetch_bitwise}")

    # ---- 2. cost x rate scaling model: GPT-1.3B tp=2 x pp=4 hybrid,
    # 8 -> 32 logical chips (dp 1 -> 4). Rates pinned explicitly so the
    # gate is deterministic on every host.
    H, L, NH, V, T = 2048, 24, 16, 50304, 2048
    TP, PP = 2, 4
    B_REP = 8                       # sequences per dp replica per step
    PEAK, HBM = 197e12, 819e9       # v5e nominal
    BUCKET_MB = float(os.environ.get("BENCH_BUCKET_MB", 25.0))
    # ONE shared pair of wire-rate constants across every lane (and
    # both uses below): duplicated inline literals would silently drift
    # and make efficiencies incomparable between the 32 and 256 lanes
    n_params = V * H + T * H + 12 * L * H * H
    link = layout.link_model(ici_gbps=DEFAULT_ICI_GBPS,
                             dcn_gbps=DEFAULT_DCN_GBPS)

    def hybrid_step_cost(n_chips, bucketed=True):
        dp = n_chips // (TP * PP)
        tokens_rep = B_REP * T
        flops_chip = 6.0 * n_params * tokens_rep / (TP * PP)
        t = CollectiveTraffic()
        # tp: Megatron 2 fwd + 2 bwd activation all-reduces per layer,
        # full [B, T, H] bf16 payload, ICI, critical-path (exposed)
        for _ in range(L):
            for _k in range(4):
                t.add("all_reduce_sum", B_REP * T * H * 2,
                      axes=(layout.tp_axis,), group_size=TP)
        # pp: microbatch activations fwd+bwd, point-to-point, pipelined
        # behind compute (overlappable)
        M = 8
        for _ in range(M):
            t.add("ppermute", (B_REP / M) * T * H * 2 * 2,
                  axes=(layout.pp_axis,), group_size=PP,
                  overlappable=True)
        # dp: grad all-reduce of this chip's param shard (f32), DCN.
        # Bucketed: the deterministic plan, every bucket but the last
        # overlapping the backward still producing later buckets.
        # Unbucketed: one monolithic reduce serialized behind the LAST
        # grad — fully exposed.
        if dp > 1:
            shard_elems = n_params // (TP * PP)
            per_layer = [((shard_elems // L,), np_.float32)
                         for _ in range(L)]
            if bucketed:
                plan = BucketPlan(per_layer, BUCKET_MB * 1e6)
                plan.traffic(op="all_reduce_sum",
                             axes=(layout.data_axis,), group_size=dp,
                             traffic=t)
            else:
                t.add("all_reduce_sum", shard_elems * 4,
                      axes=(layout.data_axis,), group_size=dp)
        return StepCost(flops=flops_chip, hbm_bytes=0.0, traffic=t,
                        link=link, peak_flops=PEAK, hbm_bps=HBM)

    c8 = hybrid_step_cost(8)
    c32 = hybrid_step_cost(32)
    c32_naive = hybrid_step_cost(32, bucketed=False)
    eff = c8.step_time_modeled_s() / c32.step_time_modeled_s()
    eff_naive = c8.step_time_modeled_s() / c32_naive.step_time_modeled_s()
    gates["scaling_efficiency_ge_85pct"] = eff >= 0.85
    # the unbucketed model must FAIL the same gate: the efficiency is
    # bought by bucketing+overlap, not by the link model being generous
    gates["naive_fails_without_overlap"] = eff_naive < 0.85
    log(f"modeled 8->32 efficiency: bucketed {eff:.3f}, "
        f"unbucketed {eff_naive:.3f}")

    # ---- 3. exposed-comm % through perf_doctor (the attribution CI
    # reads): modeled per-step records for both schedules
    import tempfile
    from paddle2_tpu.tools import perf_doctor

    def write_stream(d, cost):
        ov = cost.overlap()
        rec = {"type": "step", "rank": 0, "total_s":
               cost.step_time_modeled_s(),
               "compute_s": cost.compute_s(),
               "collective_s": ov["exposed_s"],
               "input_wait_s": 0.0, "host_s": 0.0,
               "exposed_comm_s": ov["exposed_s"]}
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for s in range(6):
                f.write(json.dumps(dict(rec, step=s)) + "\n")

    tmp = tempfile.mkdtemp(prefix="bench_scaling_")
    d_naive = os.path.join(tmp, "unbucketed")
    d_buck = os.path.join(tmp, "bucketed")
    os.makedirs(d_naive); os.makedirs(d_buck)
    write_stream(d_naive, c32_naive)
    write_stream(d_buck, c32)
    rep_naive = perf_doctor.summarize(perf_doctor.load_streams(d_naive))
    rep_buck = perf_doctor.summarize(perf_doctor.load_streams(d_buck))
    pct_naive = rep_naive["per_rank"][0]["exposed_comm_pct"]
    pct_buck = rep_buck["per_rank"][0]["exposed_comm_pct"]
    gates["exposed_comm_drops"] = pct_buck < pct_naive
    gates["perf_doctor_reports_exposed_comm"] = (
        "exposed-comm" in perf_doctor.format_summary(rep_buck, d_buck))
    log(f"exposed-comm %: unbucketed {pct_naive:.1f} -> bucketed "
        f"{pct_buck:.1f}")

    # ================== 4. THE 256-CHIP LADDER (BASELINE config 5) =====
    import math
    from paddle2_tpu.distributed.bucket import (
        DEFAULT_BUCKET_MB, bucketed_hierarchical_pmean,
        link_bucket_bytes)
    from paddle2_tpu.distributed.collective import (hierarchical_pmean,
                                                    hierarchical_psum)
    from paddle2_tpu.distributed.fleet import pipeline_spmd_1f1b
    from paddle2_tpu.kernels.pallas_matmul import (allgather_matmul,
                                                   matmul_allgather)
    from paddle2_tpu.observability.cost_model import (
        DEFAULT_DCN_LATENCY_US, DEFAULT_ICI_LATENCY_US,
        pipeline_bubble_fraction)

    # the ladder artifact reports exactly the gates THIS section adds
    # (a name-prefix filter once leaked a section-3 gate into it)
    _pre_ladder_gates = set(gates)

    # hierarchical/ring results are replicated in VALUE but typed
    # device-varying — the shared wrapper disables the rep check both
    # jax generations spell differently
    from paddle2_tpu.distributed.collective import (
        shard_map_unchecked as _sm)

    # ---- 4a. hierarchical vs flat collectives, executed on the
    # virtual mesh split 2 DCN slices x 4 ICI chips. The hierarchical
    # schedule REASSOCIATES the additions (per-slice partials first) —
    # identical elements, different tree — so the bitwise gate runs on
    # an integer-valued payload (every association sums exactly: any
    # difference is a schedule bug, not rounding) and random f32 is
    # additionally pinned to 1-ulp agreement, the same two-sided
    # contract PR 13 used for the split-K merge.
    hmesh = dist.init_mesh({"dp_dcn": 2, "dp_ici": 4})
    rs4 = np_.random.RandomState(4)
    x_int = jnp.asarray(
        rs4.randint(-64, 64, size=(37, 19)).astype(np_.float32))
    x_flt = jnp.asarray(rs4.randn(37, 19).astype(np_.float32))

    def _flat_psum(v):
        return jax.lax.psum(v, ("dp_dcn", "dp_ici"))

    def _hier_psum(v):
        return hierarchical_psum(v, "dp_ici", "dp_dcn")

    spec1 = (P(),)
    run_flat = jax.jit(_sm(_flat_psum, hmesh, spec1, P()))
    run_hier = jax.jit(_sm(_hier_psum, hmesh, spec1, P()))
    a_int = np_.asarray(run_flat(x_int))
    h_int = np_.asarray(run_hier(x_int))
    a_flt = np_.asarray(run_flat(x_flt))
    h_flt = np_.asarray(run_hier(x_flt))
    gates["hierarchical_int_bitwise_vs_flat"] = np_.array_equal(a_int,
                                                                h_int)
    gates["hierarchical_float_1ulp_vs_flat"] = bool(
        np_.allclose(a_flt, h_flt, rtol=2e-7, atol=0.0))
    # bucketed tree form: fused flat payloads over the same schedule
    tree4 = {"w": x_int, "b": jnp.asarray(
        rs4.randint(-64, 64, size=(23,)).astype(np_.float32))}
    tspec = jax.tree_util.tree_map(lambda _: P(), tree4)

    def _flat_tree(t):
        return jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, ("dp_dcn", "dp_ici")), t)

    def _hier_tree(t):
        return bucketed_hierarchical_pmean(t, "dp_ici", "dp_dcn", 512.0)

    bt_flat = jax.tree_util.tree_leaves(
        jax.jit(_sm(_flat_tree, hmesh, (tspec,), tspec))(tree4))
    bt_hier = jax.tree_util.tree_leaves(
        jax.jit(_sm(_hier_tree, hmesh, (tspec,), tspec))(tree4))
    gates["hierarchical_bucketed_int_bitwise"] = all(
        np_.array_equal(np_.asarray(p), np_.asarray(q))
        for p, q in zip(bt_flat, bt_hier))
    log(f"hierarchical vs flat: int bitwise="
        f"{gates['hierarchical_int_bitwise_vs_flat']}, float 1-ulp="
        f"{gates['hierarchical_float_1ulp_vs_flat']}, bucketed="
        f"{gates['hierarchical_bucketed_int_bitwise']}")

    # ---- 4b. interleaved-VPP: v>1 vs v=1 of the SAME 8-virtual-stage
    # model, bitwise (the interleaving is a pure schedule shape)
    rs5 = np_.random.RandomState(5)
    PV, BV, DV, MV = 8, 4, 16, 8
    Wp = jnp.asarray(rs5.randn(PV, DV, DV).astype(np_.float32) * 0.3)
    bp = jnp.asarray(rs5.randn(PV, DV).astype(np_.float32) * 0.1)
    xp = jnp.asarray(rs5.randn(MV, BV, DV).astype(np_.float32))
    yp = jnp.asarray(rs5.randn(MV, BV, DV).astype(np_.float32))

    def _stage(pv, shared, xx, sidx):
        Wl, bl = pv
        return jnp.tanh(xx @ Wl + bl)

    def _sloss(out, lab):
        return ((out - lab) ** 2).mean()

    dist.init_mesh({"pp": 8})
    l_v1, g_v1 = pipeline_spmd_1f1b(_stage, (Wp, bp), xp, yp, _sloss)
    dist.init_mesh({"pp": 4, "dp": 2})
    l_v2, g_v2 = pipeline_spmd_1f1b(_stage, (Wp, bp), xp, yp, _sloss,
                                    virtual_stages=2)
    gates["vpp_v2_bitwise_vs_v1"] = (
        np_.float32(l_v1) == np_.float32(l_v2)
        and all(np_.array_equal(np_.asarray(p), np_.asarray(q))
                for p, q in zip(g_v1, g_v2)))
    # composed with dp + bucketed grad reduce (the ladder's actual
    # schedule shape): v=2 x dp=2 vs v=1 x dp=2, bitwise
    dist.init_mesh({"pp": 4, "dp": 2})
    l_d1, g_d1 = pipeline_spmd_1f1b(_stage, (Wp[:4], bp[:4]), xp, yp,
                                    _sloss, dp_axis="dp")
    dist.init_mesh({"pp": 2, "dp": 2, "mp": 2})
    l_d2, g_d2 = pipeline_spmd_1f1b(_stage, (Wp[:4], bp[:4]), xp, yp,
                                    _sloss, dp_axis="dp",
                                    virtual_stages=2,
                                    grad_bucket_bytes=512.0)
    gates["vpp_dp_bucketed_bitwise"] = (
        np_.float32(l_d1) == np_.float32(l_d2)
        and all(np_.array_equal(np_.asarray(p), np_.asarray(q))
                for p, q in zip(g_d1, g_d2)))
    log(f"interleaved-VPP: v2-vs-v1 bitwise="
        f"{gates['vpp_v2_bitwise_vs_v1']}, dp+buckets composed="
        f"{gates['vpp_dp_bucketed_bitwise']}")

    # ---- 4c. collective matmul: fused vs unfused, bitwise (both the
    # input-gather ring and the epilogue output-gather form)
    cmesh = dist.init_mesh({"mp": 4, "dp": 2})
    rs6 = np_.random.RandomState(6)
    xa = jnp.asarray(rs6.randn(32, 24).astype(np_.float32))
    wa = jnp.asarray(rs6.randn(24, 16).astype(np_.float32))
    wb = jnp.asarray(rs6.randn(24, 32).astype(np_.float32))

    def _ag_unfused(xs, ww):
        return jax.lax.all_gather(xs, "mp", axis=0, tiled=True) @ ww

    def _ag_fused(xs, ww):
        return allgather_matmul(xs, ww, "mp")

    u_in = np_.asarray(jax.jit(_sm(_ag_unfused, cmesh,
                                   (P("mp"), P()), P()))(xa, wa))
    f_in = np_.asarray(jax.jit(_sm(_ag_fused, cmesh,
                                   (P("mp"), P()), P()))(xa, wa))
    gates["collective_matmul_input_bitwise"] = np_.array_equal(u_in,
                                                               f_in)

    def _ep_unfused(xx, ws):
        return jax.lax.all_gather(xx @ ws, "mp", axis=1, tiled=True)

    def _ep_fused(xx, ws):
        return matmul_allgather(xx, ws, "mp", tiles=4)

    u_ep = np_.asarray(jax.jit(_sm(_ep_unfused, cmesh,
                                   (P(), P(None, "mp")), P()))(xa, wb))
    f_ep = np_.asarray(jax.jit(_sm(_ep_fused, cmesh,
                                   (P(), P(None, "mp")), P()))(xa, wb))
    gates["collective_matmul_epilogue_bitwise"] = np_.array_equal(u_ep,
                                                                  f_ep)
    log(f"collective matmul: input-gather bitwise="
        f"{gates['collective_matmul_input_bitwise']}, epilogue bitwise="
        f"{gates['collective_matmul_epilogue_bitwise']}")

    # ---- 4d. DCN-aware bucket sizing: pure deterministic function of
    # (param order, link class); the latency-dominated DCN hop must
    # pick a strictly larger target than ICI under the alpha+beta model
    alink = layout.link_model(
        ici_gbps=DEFAULT_ICI_GBPS, dcn_gbps=DEFAULT_DCN_GBPS,
        ici_latency_us=DEFAULT_ICI_LATENCY_US,
        dcn_latency_us=DEFAULT_DCN_LATENCY_US)
    tgt_ici = link_bucket_bytes(alink, (layout.fsdp_axis,))
    tgt_dcn = link_bucket_bytes(alink, (layout.data_axis,))
    gates["dcn_bucket_target_gt_ici"] = tgt_dcn > tgt_ici
    lad_avals = [((1024, 1024), np_.float32) for _ in range(64)]
    pl_a = plan_buckets(lad_avals, tgt_dcn)
    pl_b = plan_buckets(list(lad_avals), tgt_dcn)
    gates["dcn_plan_deterministic"] = pl_a == pl_b
    info["bucket_targets_mb"] = {"ici": round(tgt_ici / 1e6, 3),
                                 "dcn": round(tgt_dcn / 1e6, 3)}

    # ---- 4e. the modeled ladder itself: ERNIE-3.0-XL-class ZeRO-3
    # across DCN slices. Geometry: tp=2 x pp=4 model-parallel group
    # (constant across rungs so per-chip work is constant — weak
    # scaling), ZeRO-3/fsdp=4 within the 32-chip ICI slice, dp across
    # DCN slices: 8 -> 32 -> 64 -> 128 -> 256 chips.
    H5, L5, V5, T5 = 2560, 32, 50304, 2048
    TP5, PP5, FSDP5 = 2, 4, 4
    M5, VS5 = 16, 4                 # microbatches, virtual stages
    B5 = 16                         # seqs per model-parallel group
    n_params5 = V5 * H5 + T5 * H5 + 12 * L5 * H5 * H5
    grad_bytes5 = n_params5 // (TP5 * PP5) * 4      # f32 grads/chip
    ag_bytes5 = n_params5 // (TP5 * PP5) * 2        # bf16 params/chip
    # the non-DCN-aware baseline bucket: what an ALPHA-BLIND
    # (bandwidth-only, i.e. pre-ladder) cost model prefers. With
    # dispatches free, shrinking buckets strictly improves the model
    # (same total bytes, smaller exposed tail, finer overlap) — so an
    # alpha-blind autotuner walks DOWN from the 25 MB default toward
    # fine-grained buckets; 4 MB stands in for that optimum. The gate
    # below DEMONSTRATES the preference rather than asserting it, so
    # this baseline is an honest alternative, not a strawman.
    ICI_SIZED_BUCKET = 4e6
    fsdp_ax, dcn_ax = layout.fsdp_axis, layout.data_axis

    def ladder_step_cost(n_chips, hierarchical=True, vpp=True,
                         dcn_buckets=True, collective_mm=True,
                         grad_bucket=None, link=None):
        link = link if link is not None else alink
        fsdp = min(FSDP5, n_chips // (TP5 * PP5))
        dcn = n_chips // (TP5 * PP5 * fsdp)
        flops_chip = 6.0 * n_params5 * (B5 * T5) / (TP5 * PP5)
        bubble = pipeline_bubble_fraction(PP5, M5, VS5 if vpp else 1)
        t = CollectiveTraffic()
        # tp activation collectives: Megatron 4 per layer per
        # microbatch, [B_micro, T, H] bf16 — hidden inside MXU time by
        # the collective matmul, on the critical path without it
        tp_payload = (B5 // M5) * T5 * H5 * 2
        for _ in range(M5 * (L5 // PP5) * 4):
            t.add("all_reduce_sum", tp_payload, axes=(layout.tp_axis,),
                  group_size=TP5, overlappable=collective_mm)
        if fsdp > 1:
            # ZeRO-3 param all-gather, one dispatch per layer group per
            # pass (fwd + bwd regather), prefetch-overlapped (PR 8)
            n_ag = 2 * (L5 // PP5)
            for _ in range(n_ag):
                t.add("all_gather", ag_bytes5 / (L5 // PP5),
                      axes=(fsdp_ax,), group_size=fsdp,
                      overlappable=True)
        if fsdp * dcn > 1:
            if hierarchical and dcn > 1:
                # hierarchical grad sync, bucketed: in-slice ICI
                # reduce-scatter, cross-slice DCN all-reduce of the
                # 1/fsdp partials, in-slice all-gather. Bucket size
                # targets the LATENCY-DOMINATED hop: the DCN dispatch
                # carries bucket/fsdp bytes, so the full-tensor bucket
                # is fsdp x the per-link target
                tgt = (grad_bucket if grad_bucket is not None
                       else tgt_dcn if dcn_buckets else ICI_SIZED_BUCKET)
                bucket = tgt * fsdp
                n_b = max(1, math.ceil(grad_bytes5 / bucket))
                for i in range(n_b):
                    b = min(bucket, grad_bytes5 - i * bucket)
                    t.add_hierarchical_all_reduce(
                        b, ici_axes=(fsdp_ax,), dcn_axes=(dcn_ax,),
                        ici_group=fsdp, dcn_group=dcn,
                        overlappable=i < n_b - 1)
            elif dcn == 1:
                # single slice: plain bucketed ZeRO grad reduce on ICI
                tgt = tgt_ici if dcn_buckets else ICI_SIZED_BUCKET
                n_b = max(1, math.ceil(grad_bytes5 / tgt))
                for i in range(n_b):
                    b = min(tgt, grad_bytes5 - i * tgt)
                    t.add("all_reduce_sum", b, axes=(fsdp_ax,),
                          group_size=fsdp, overlappable=i < n_b - 1)
            else:
                # FLAT: the PR 8 machinery as it exists — bucketed,
                # overlap-capable — but reduced over the combined
                # (fsdp x dcn) group, so EVERY byte is charged at the
                # slow DCN hop and every bucket dispatch pays the DCN
                # setup latency (alpha is always exposed). This is the
                # honest non-hierarchical baseline: the hierarchy's
                # win is moving the bulk of the bytes (and dispatches)
                # onto ICI, not the bucketing itself.
                tgt = tgt_dcn if dcn_buckets else ICI_SIZED_BUCKET
                n_b = max(1, math.ceil(grad_bytes5 / tgt))
                for i in range(n_b):
                    b = min(tgt, grad_bytes5 - i * tgt)
                    t.add("all_reduce_sum", b,
                          axes=(fsdp_ax, dcn_ax), group_size=fsdp * dcn,
                          overlappable=i < n_b - 1)
        return StepCost(flops=flops_chip * (1.0 + bubble),
                        hbm_bytes=0.0, traffic=t, link=link,
                        peak_flops=PEAK, hbm_bps=HBM)

    RUNGS = (8, 32, 64, 128, 256)
    base8 = ladder_step_cost(8)
    t8 = base8.step_time_modeled_s()
    ladder_rows = []
    for n_chips in RUNGS:
        c_full = ladder_step_cost(n_chips)
        c_flat = ladder_step_cost(n_chips, hierarchical=False,
                                  vpp=False, dcn_buckets=False,
                                  collective_mm=False)
        by_cls = c_full.exposed_network_by_class()
        ladder_rows.append({
            "chips": n_chips,
            "efficiency": round(t8 / c_full.step_time_modeled_s(), 4),
            "efficiency_flat": round(
                t8 / c_flat.step_time_modeled_s(), 4),
            "modeled_step_ms": round(
                c_full.step_time_modeled_s() * 1e3, 2),
            "modeled_step_flat_ms": round(
                c_flat.step_time_modeled_s() * 1e3, 2),
            "exposed_ici_ms": round(by_cls["ici"] * 1e3, 3),
            "exposed_dcn_ms": round(by_cls["dcn"] * 1e3, 3),
        })
    c256 = ladder_step_cost(256)
    c256_flat = ladder_step_cost(256, hierarchical=False, vpp=False,
                                 dcn_buckets=False, collective_mm=False)
    eff_256 = t8 / c256.step_time_modeled_s()
    eff_256_flat = t8 / c256_flat.step_time_modeled_s()
    # lever attribution: drop ONE lever at a time — each must strictly
    # reduce the 8->256 efficiency (load-bearing, not decorative)
    levers = {}
    for name, kw in (
            ("hierarchical", {"hierarchical": False}),
            ("vpp", {"vpp": False}),
            ("dcn_buckets", {"dcn_buckets": False}),
            ("collective_matmul", {"collective_mm": False})):
        levers[name] = round(
            t8 / ladder_step_cost(256, **kw).step_time_modeled_s(), 4)
    gates["ladder_efficiency_8_to_256_ge_90pct"] = eff_256 >= 0.90
    gates["ladder_flat_fails_gate"] = eff_256_flat < 0.90
    gates["ladder_every_rung_ge_90pct"] = all(
        r["efficiency"] >= 0.90 for r in ladder_rows)
    gates["ladder_every_lever_load_bearing"] = all(
        v < round(eff_256, 4) for v in levers.values())
    # the schedule levers must each individually sink the gate
    gates["ladder_vpp_required"] = levers["vpp"] < 0.90
    gates["ladder_collective_matmul_required"] = (
        levers["collective_matmul"] < 0.90)
    # the hierarchy's specific claim: the slow wire carries a FRACTION
    # of the bytes — serial DCN wire time of the non-hierarchical grad
    # sync must exceed the hierarchical one by at least the in-slice
    # aggregation factor's worth (>= 3x here; the exact ratio rides the
    # wire-factor difference between the two algorithms)
    dcn_serial_hier = c256.traffic.overlap_split_by_class(
        alink, c256.compute_s())["dcn"]["serial_s"]
    c256_nohier = ladder_step_cost(256, hierarchical=False)
    dcn_serial_flat = c256_nohier.traffic.overlap_split_by_class(
        alink, c256_nohier.compute_s())["dcn"]["serial_s"]
    gates["ladder_hierarchical_dcn_wire_reduced_3x"] = (
        dcn_serial_flat >= 3.0 * dcn_serial_hier)
    # the DCN-bucket lever's honesty check: under an ALPHA-BLIND
    # (zero-latency) link model the fine ICI-era bucket is at least as
    # good as the 25 MB default (same bytes, smaller exposed tail) —
    # i.e. a pre-ladder autotuner genuinely prefers the baseline this
    # lever is compared against; only the alpha term makes it lose
    link0 = layout.link_model(ici_gbps=DEFAULT_ICI_GBPS,
                              dcn_gbps=DEFAULT_DCN_GBPS)
    t_fine_blind = ladder_step_cost(
        256, grad_bucket=ICI_SIZED_BUCKET,
        link=link0).step_time_modeled_s()
    t_dflt_blind = ladder_step_cost(
        256, grad_bucket=DEFAULT_BUCKET_MB * 1e6,
        link=link0).step_time_modeled_s()
    gates["alpha_blind_model_prefers_fine_buckets"] = (
        t_fine_blind <= t_dflt_blind)
    log(f"256 ladder: eff_full={eff_256:.4f} eff_flat={eff_256_flat:.4f}"
        f" levers={levers} dcn_serial flat/hier = "
        f"{dcn_serial_flat * 1e3:.1f}/{dcn_serial_hier * 1e3:.1f} ms")

    # ---- 4f. 256-chip kill-and-rescale drill, priced end to end: a
    # chip dies mid-step; detect (PR 5 prober cadence) -> quarantine
    # verdict (PR 5 store) -> gang re-formation gossip (log2 fan-in) ->
    # buddy-replica shard fetch over DCN (PR 4 ladder; ckpt reshard
    # narrowing is the fallback) -> warm-cache recompile (PR 6 measured
    # hit) -> one replayed step. Every term is a constant, a log, or a
    # fixed shard transfer — so MTTR grows SUBLINEARLY in world size,
    # which is the gate.
    PROBE_S = 1.0                   # health-prober cadence (PR 5)
    QUARANTINE_S = 0.05             # store write + verdict
    GOSSIP_PER_ROUND_S = 0.1        # rendezvous fan-in per log2 round
    COMPILE_HIT_S = 0.29            # PR 6 measured warm-cache restart
    shard_bytes = 3 * 4 * n_params5 // (TP5 * PP5 * FSDP5)

    def rescale_drill(n_chips):
        fetch_s = alink.seconds(shard_bytes, (dcn_ax,))
        replay_s = ladder_step_cost(n_chips).step_time_modeled_s()
        comp = {
            "detect_s": PROBE_S,
            "quarantine_s": QUARANTINE_S,
            "rendezvous_s": GOSSIP_PER_ROUND_S * math.log2(n_chips),
            "replica_fetch_s": round(fetch_s, 4),
            "compile_s": COMPILE_HIT_S,
            "replay_step_s": round(replay_s, 4),
        }
        comp["mttr_s"] = round(sum(comp.values()), 4)
        return comp

    drills = {n: rescale_drill(n) for n in (32, 64, 128, 256)}
    mttr_ratios = [drills[b]["mttr_s"] / drills[a]["mttr_s"]
                   for a, b in ((32, 64), (64, 128), (128, 256))]
    mttr_budget = float(os.environ.get("BENCH_MTTR_BUDGET_S", "60"))
    gates["rescale_mttr_sublinear"] = all(r < 1.25 for r in mttr_ratios)
    gates["rescale_mttr_under_budget"] = (
        drills[256]["mttr_s"] <= mttr_budget)
    log(f"kill-and-rescale: MTTR 32->256 = "
        f"{drills[32]['mttr_s']:.2f}s -> {drills[256]['mttr_s']:.2f}s "
        f"(doubling ratios {[round(r, 3) for r in mttr_ratios]})")

    # ---- 4g. ici/dcn-split perf_doctor streams + byte-identical
    # artifact (what the CI smoke job runs twice, cmps, and diffs)
    def write_ladder_stream(d, cost):
        os.makedirs(d, exist_ok=True)
        ov = cost.overlap()
        cls = cost.exposed_network_by_class()
        rec = {"type": "step", "rank": 0,
               "total_s": cost.step_time_modeled_s(),
               "compute_s": cost.compute_s(),
               "collective_s": ov["exposed_s"],
               "input_wait_s": 0.0, "host_s": 0.0,
               "exposed_comm_s": ov["exposed_s"],
               "exposed_comm_ici_s": cls["ici"],
               "exposed_comm_dcn_s": cls["dcn"]}
        with open(os.path.join(d, "metrics_rank_0.jsonl"), "w") as f:
            for st in range(6):
                f.write(json.dumps(dict(rec, step=st),
                                   sort_keys=True) + "\n")

    lad_dir = bench_scratch("multichip_256",
                            env_var="BENCH_MULTICHIP_METRICS_DIR")
    d_full = os.path.join(lad_dir, "full")
    d_flat = os.path.join(lad_dir, "flat")
    write_ladder_stream(d_full, c256)
    write_ladder_stream(d_flat, c256_flat)
    rep_full = perf_doctor.summarize(perf_doctor.load_streams(d_full))
    rep_flat = perf_doctor.summarize(perf_doctor.load_streams(d_flat))
    agg_full = rep_full["aggregate"]
    agg_flat = rep_flat["aggregate"]
    gates["perf_doctor_splits_ici_dcn"] = (
        "exposed_comm_ici_pct" in agg_full
        and "exposed_comm_dcn_pct" in agg_full)
    gates["flat_dcn_exposure_grows"] = (
        agg_flat.get("exposed_comm_dcn_pct", 0.0)
        > agg_full.get("exposed_comm_dcn_pct", 0.0))
    diff_text = perf_doctor.format_diff(
        perf_doctor.diff(rep_full, rep_flat))
    gates["perf_doctor_names_dcn_regression"] = (
        "DCN" in diff_text and "OVERLAP REGRESSION" in diff_text)
    log(f"perf_doctor split: full ici/dcn = "
        f"{agg_full.get('exposed_comm_ici_pct', 0.0):.2f}%/"
        f"{agg_full.get('exposed_comm_dcn_pct', 0.0):.2f}%, flat dcn = "
        f"{agg_flat.get('exposed_comm_dcn_pct', 0.0):.2f}%")

    ladder_artifact = {
        "config": "BASELINE 5: ERNIE-3.0-XL-class ZeRO-3 across DCN "
                  "slices (tp=2 x pp=4 x fsdp=4 per 32-chip slice, "
                  "dp over DCN)",
        "geometry": {"hidden": H5, "layers": L5, "vocab": V5,
                     "seq": T5, "params_b": round(n_params5 / 1e9, 2),
                     "tp": TP5, "pp": PP5, "fsdp": FSDP5,
                     "microbatches": M5, "virtual_stages": VS5,
                     "seqs_per_replica": B5},
        "rates": {"peak_tflops": PEAK / 1e12,
                  "ici_gbps": DEFAULT_ICI_GBPS,
                  "dcn_gbps": DEFAULT_DCN_GBPS,
                  "ici_latency_us": DEFAULT_ICI_LATENCY_US,
                  "dcn_latency_us": DEFAULT_DCN_LATENCY_US},
        "bucket_targets_mb": info["bucket_targets_mb"],
        "bubble_fraction": {
            "v1": round(pipeline_bubble_fraction(PP5, M5, 1), 4),
            f"v{VS5}": round(
                pipeline_bubble_fraction(PP5, M5, VS5), 4)},
        "ladder": ladder_rows,
        "efficiency_8_to_256": round(eff_256, 4),
        "efficiency_8_to_256_flat": round(eff_256_flat, 4),
        "lever_attribution_eff_256": levers,
        "rescale_drill": drills,
        "mttr_doubling_ratios": [round(r, 4) for r in mttr_ratios],
        "gates": {k: v for k, v in gates.items()
                  if k not in _pre_ladder_gates},
    }
    artifact_path = os.environ.get("BENCH_MULTICHIP_ARTIFACT",
                                   "MULTICHIP_256_r01.json")
    write_artifact(artifact_path, ladder_artifact, indent=1,
                   sort_keys=True, trailing_newline=True)
    log(f"ladder artifact -> {artifact_path}")

    ok = all(gates.values())
    print(json.dumps({
        "metric": "multichip_scaling_efficiency_8_to_256",
        "value": round(eff_256, 4),
        "unit": "modeled step-time ratio (cost x rate, zero wall-clock "
                "A/B)",
        "ladder_256": {
            "efficiency_8_to_256": round(eff_256, 4),
            "efficiency_8_to_256_flat": round(eff_256_flat, 4),
            "lever_attribution": levers,
            "mttr_s_256": drills[256]["mttr_s"],
            "artifact": artifact_path,
        },
        "efficiency_8_to_32_config4": round(eff, 4),
        "scaling": {
            "config": "BASELINE 4: GPT-1.3B tp=2 x pp=4, dp 1->4 "
                      "(8->32 logical chips)",
            "efficiency_bucketed": round(eff, 4),
            "efficiency_unbucketed": round(eff_naive, 4),
            "modeled_step_ms": {
                "chips8": round(c8.step_time_modeled_s() * 1e3, 2),
                "chips32": round(c32.step_time_modeled_s() * 1e3, 2),
                "chips32_unbucketed":
                    round(c32_naive.step_time_modeled_s() * 1e3, 2)},
            "exposed_comm_pct": {"unbucketed": round(pct_naive, 1),
                                 "bucketed": round(pct_buck, 1)},
            "per_chip_flops": c8.flops,
            "wire_bytes_per_chip_32": round(
                c32.traffic.wire_bytes_total()),
            "bucket_mb": BUCKET_MB,
            "rates": {"peak_tflops": PEAK / 1e12,
                      "ici_gbps": DEFAULT_ICI_GBPS,
                      "dcn_gbps": DEFAULT_DCN_GBPS,
                      "dcn_axes": list(layout.dcn_axes)},
            "geometry": {"hidden": H, "layers": L, "heads": NH,
                         "vocab": V, "seq": T,
                         "params_b": round(n_params / 1e9, 2)},
        },
        "parity": {"bucketed_grads_bitwise": bucketed_bitwise,
                   "zero3_prefetch_bitwise": prefetch_bitwise,
                   "bucket_dispatches": info["bucket_dispatches"]},
        "gates": gates,
        "ok": ok,
        "note": "parity executed on the 8-virtual-device CPU mesh; "
                "32-chip figures are deterministic cost x rate "
                "(collective bytes x link model) — wall-clock is "
                "unreliable in this sandbox",
    }))
    return 0 if ok else 1


def bench_serving():
    """Production serving gate: continuous batching + paged KV vs the
    one-request-at-a-time Predictor loop, fully deterministic (XLA
    cost model x seeded Poisson trace — ZERO wall-clock anywhere).

    Gates (ISSUE 9 acceptance):
      1. aggregate tokens/s >= 3x the Predictor baseline under the
         same modeled load,
      2. p99 TTFT under the load bound (10x the per-request floor of
         prefill + one decode step — a stable-queue bound: offered
         load is pinned at 5x baseline capacity, well under the
         batch-8 engine's capacity),
      3. KV high-water mark <= 55% of the contiguous max-seq-len
         cache a non-paged engine reserves for the same batch,
      4. compiled decode program count <= the fixed bucket budget
         (no per-composition recompiles).
    Writes the serving metrics stream (step records carry EXPLICIT
    tokens + modeled_step_s) for perf_doctor, and SERVING_r01.json.
    """
    import paddle2_tpu as paddle
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle2_tpu.observability import metrics
    from paddle2_tpu.serving import (EngineConfig, ServingEngine,
                                     poisson_trace, simulate_serving,
                                     simulate_predictor_baseline)
    from paddle2_tpu.serving.simulate import cost_seconds

    metrics_dir = bench_scratch("serving_metrics",
                                env_var="BENCH_SERVING_METRICS_DIR")
    paddle.seed(0)
    # WIDTH CALIBRATION (PR 21). The gates below state what continuous
    # batching buys when a decode step is dominated by bytes that do
    # not grow with the batch — on a chip, the weight stream. At
    # gpt_tiny's hidden 64 the weights are 0.5 MB and the cost model
    # (XLA cost analysis of the UNOPTIMIZED program) prices the per-row
    # elementwise work — mostly the unfused erf-GELU chain — at ~0.55
    # MB per row, so a batch-8 step costs 4.1x a batch-1 step and no
    # load can show 3x. Until PR 21 the lane passed at hidden 64 only
    # because the decode program copied a layer out of the KV pool
    # every step — 2.4 MB of row-independent bytes that stood in for a
    # weight stream; the chip-shaped kernel reads the pool in place.
    # Hidden 512 (25 MB of weights, a batch-8 step 2.0x a batch-1 step)
    # is in the regime the gates are about; 8 heads x 64 is the served
    # head geometry (two heads per 128-lane page).
    # max_position_embeddings must cover max_model_len=128 — the
    # engine validates it (clamped wpe gathers would silently corrupt)
    cfg = gpt_tiny(use_scan=False, hidden_size=512, num_heads=8,
                   max_position_embeddings=128)
    model = GPTForCausalLM(cfg)

    def make_engine():
        return ServingEngine(model, config=EngineConfig(
            block_size=16, num_blocks=40, max_batch=8,
            prefill_budget_tokens=64, max_model_len=128))

    prompt_lens, gen_tokens = [16, 24], [12, 24]
    mean_gen = float(np.mean(gen_tokens))

    # -- phase 1: probe the cost model (compiles prefill + b1 decode),
    #    then derive the OFFERED LOAD from the baseline's own modeled
    #    capacity: 5x over it saturates one-at-a-time serving while
    #    staying under the batch-8 engine's ~8x headroom
    probe = make_engine()
    probe_trace = poisson_trace(2, rate_per_s=100.0,
                                prompt_lens=prompt_lens,
                                gen_tokens=gen_tokens,
                                vocab=cfg.vocab_size, seed=1)
    simulate_serving(probe, probe_trace)
    b1_key = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1_key))
    prefill_s = max(cost_seconds(c)
                    for c in probe.runner._prefill_costs.values())
    base_token_capacity = 1.0 / decode_s
    offered_tokens_per_s = 5.0 * base_token_capacity
    rate_req = offered_tokens_per_s / mean_gen
    log(f"serving probe: decode_s={decode_s*1e6:.1f}us "
        f"prefill_s={prefill_s*1e6:.1f}us "
        f"offered={offered_tokens_per_s:,.0f} tok/s "
        f"({rate_req:,.1f} req/s)")

    # -- phase 2: the measured run, metrics plane on
    metrics.enable(metrics_dir, rank=0, flush_steps=1)
    engine = make_engine()
    trace = poisson_trace(40, rate_per_s=rate_req,
                          prompt_lens=prompt_lens, gen_tokens=gen_tokens,
                          vocab=cfg.vocab_size, seed=7)
    rep = simulate_serving(engine, trace)
    base = simulate_predictor_baseline(engine, trace)
    metrics.flush()
    metrics.export_prometheus()
    metrics.disable()

    ratio = rep.tokens_per_s / max(base.tokens_per_s, 1e-12)
    ttft_bound = 10.0 * (prefill_s + decode_s)
    gates = {
        "tokens_per_s_3x_baseline": ratio >= 3.0,
        "p99_ttft_under_bound": rep.p99_ttft_s <= ttft_bound,
        "kv_high_water_le_55pct": rep.kv_ratio <= 0.55,
        "decode_programs_bounded":
            rep.decode_programs <= rep.program_budget,
    }
    log(f"serving: CB {rep.tokens_per_s:,.0f} tok/s vs baseline "
        f"{base.tokens_per_s:,.0f} (ratio {ratio:.2f}, gate >= 3)")
    log(f"serving: p99 TTFT {rep.p99_ttft_s*1e3:.3f}ms "
        f"(bound {ttft_bound*1e3:.3f}ms)  mean occupancy "
        f"{rep.mean_batch_occupancy:.2f}  evictions {rep.evictions}")
    log(f"serving: KV high water {rep.kv_high_water_bytes:,}B = "
        f"{100*rep.kv_ratio:.1f}% of contiguous "
        f"{rep.contiguous_cache_bytes:,}B (gate <= 55%)")
    log(f"serving: decode programs {rep.decode_programs} <= budget "
        f"{rep.program_budget}")
    result = {
        "metric": "serving_tokens_per_s_vs_predictor",
        "value": round(ratio, 3), "unit": "x",
        "tokens_per_s": round(rep.tokens_per_s, 1),
        "baseline_tokens_per_s": round(base.tokens_per_s, 1),
        "p99_ttft_ms": round(rep.p99_ttft_s * 1e3, 4),
        "ttft_bound_ms": round(ttft_bound * 1e3, 4),
        "mean_ttft_ms": round(rep.mean_ttft_s * 1e3, 4),
        "kv_high_water_ratio": round(rep.kv_ratio, 4),
        "decode_programs": rep.decode_programs,
        "program_budget": rep.program_budget,
        "mean_batch_occupancy": round(rep.mean_batch_occupancy, 3),
        "evictions": rep.evictions,
        "decode_steps": rep.decode_steps,
        "offered_tokens_per_s": round(offered_tokens_per_s, 1),
        "gates": gates,
    }
    return emit_result("serving", "SERVING_r01.json", result)


def bench_serving_reliability():
    """``--serving-reliability``: the serving robustness gate (ISSUE
    11) — ported onto the declarative ``bench/scenarios`` registry
    (ISSUE 17): the drills, gates, streams, and artifact bytes are
    unchanged; see ``bench/scenarios/serving_reliability.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("serving-reliability")


def bench_fleet_kv():
    """``--fleet-kv``: the fleet-global KV resilience gate (ISSUE
    16) — ported onto the declarative ``bench/scenarios`` registry
    (ISSUE 17): the drills, gates, streams, and artifact bytes are
    unchanged; see ``bench/scenarios/fleet_kv.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("fleet-kv")


def bench_ps_recommender():
    """``--ps-recommender``: the ISSUE 18 tentpole — the fault-tolerant
    parameter-server plane (hash-ring shards, primary+follower
    replication, server-kill failover, bounded staleness, hot-key
    follower caching), every drill on the virtual cost-model clock.
    See ``bench/scenarios/ps_recommender.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("ps-recommender")


def bench_moe_training():
    """``--moe-training``: the ISSUE 19 tentpole — fault-tolerant
    expert-parallel MoE training (hash-ring expert placement,
    host-kill failover with bitwise replay, priced hierarchical
    all-to-all, router-collapse watchdog, exact token-conservation
    ledger), every drill on the virtual cost-model clock.
    See ``bench/scenarios/moe_training.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("moe-training")


def bench_long_context():
    """``--long-context``: the ISSUE 20 tentpole — fault-tolerant
    sequence-parallel training (hash-ring K/V shard placement,
    chaos-hardened ring attention with mid-pass kill healed by ring
    re-formation and bitwise step replay, exact LSE-merge conservation
    ledger, 32k ring/Ulysses schedule budgets gated both ways), every
    drill on the virtual cost-model clock.
    See ``bench/scenarios/long_context.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("long-context")


def bench_million_user_day():
    """``--million-user-day``: the ISSUE 17 tentpole — one closed-loop
    train->serve day on the deterministic cost-model clock, chaos
    armed end to end, headline = modeled cost per served token; see
    ``bench/scenarios/million_user_day.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("million-user-day")


def bench_tracing():
    """``--tracing``: request-lifecycle tracing + exact tail-latency
    attribution (ISSUE 13) — ported onto the declarative
    ``bench/scenarios`` registry (ISSUE 20 satellite): the drills,
    gates, streams, and artifact bytes are unchanged; see
    ``bench/scenarios/tracing.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("tracing")


def bench_serving_throughput():
    """``--serving-throughput``: the per-token economics gate (ISSUE
    14) — copy-on-write prefix caching, speculative decoding, and the
    online-softmax/split-K flash-decode kernel, all deterministic
    (XLA cost model x seeded traces x virtual clock — ZERO wall-clock
    anywhere; run twice, SERVING_THROUGHPUT_r01.json is
    byte-identical).

    Gates:
      1. **Prefix caching** — a shared-system-prompt trace (48-token
         system prefix, per-request suffixes padding to the SAME
         prefill bucket so cached KV is bitwise what a private
         prefill would write): KV bytes/request (allocator handouts,
         shares are free) reduced >= 2x vs the no-sharing run, with
         token-CRC equality — sharing is exact, not approximate.
      2. **Speculation** — an acceptance-controlled oracle drafter
         pinned at 70%: modeled tokens/s uplift >= 1.5x vs the
         non-speculative run on a decode-bound trace, token-CRC
         equality (wrong drafts are REJECTED by the in-program
         verify; the stream never changes), measured acceptance
         within 2 points of the 70% setpoint.
      3. **32k kernel** — deterministic accounting under pinned v5e
         rates: the PR 9 single-softmax kernel's whole-context VMEM
         scratch CANNOT fit at 32k (feasible=False — it has no
         latency to model), the split-K kernel fits and its modeled
         decode latency stays within 1.25x the pure KV-read roofline;
         the split body EXECUTES bitwise (fp32) against its dense
         mirrored reference and allclose against the global-softmax
         reference at a multi-split context.
      4. **int4 weight-only** (ROADMAP item 4 satellite) — the
         analytic error bound HOLDS at 4 bits against an f64
         reference AND is NON-VACUOUS (a 2-bit payload violates it;
         it beats the trivial |y| bound), through the packed-nibble
         storage path.
      5. **PR 11/12 composition** — the four reliability drills
         (kill / transient / overload / hot-swap) run with prefix
         caching + speculation ENABLED: token-for-token vs their
         clean twins, allocator + prefix-cache ledger drains clean,
         and the PR 12 integer-picosecond decomposition identity
         stays exact on every finished request.
    """
    import io
    import shutil
    import zlib
    from contextlib import redirect_stdout

    import jax.numpy as jnp
    import numpy as np_
    import paddle2_tpu as paddle
    from paddle2_tpu.distributed.fault_tolerance import chaos
    from paddle2_tpu.kernels import pallas_matmul as pm
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    from paddle2_tpu.observability import metrics, tracing
    from paddle2_tpu.serving import (
        EngineConfig, EngineFailoverRouter, HotSwapController,
        ReliabilityConfig, ServingEngine, SpeculativeConfig,
        paged_attention_decode, paged_attention_reference,
        paged_attention_split_reference, simulate_router,
        simulate_serving, poisson_trace)
    from paddle2_tpu.serving import paged_attention as pa
    from paddle2_tpu.serving.simulate import cost_seconds
    from paddle2_tpu.tools import perf_doctor, serve_doctor

    metrics_dir = bench_scratch(
        "serving_throughput_metrics",
        env_var="BENCH_SERVING_THROUGHPUT_METRICS_DIR")
    trace_root = bench_scratch(
        "serving_throughput_traces",
        env_var="BENCH_SERVING_THROUGHPUT_TRACE_DIR")
    for d in (metrics_dir, trace_root):
        shutil.rmtree(d, ignore_errors=True)   # streams append

    paddle.seed(0)
    cfg = gpt_tiny(use_scan=False, max_position_embeddings=128)
    model = GPTForCausalLM(cfg)
    VOCAB = cfg.vocab_size
    gates = {}

    def make_engine(prefix=False, spec=None, reliability=None,
                    num_blocks=64):
        return ServingEngine(model, config=EngineConfig(
            block_size=16, num_blocks=num_blocks, max_batch=8,
            prefill_budget_tokens=128, max_model_len=128,
            enable_prefix_cache=prefix, spec=spec,
            reliability=reliability))

    # ---- shared-system-prompt trace: every prompt = 48-token system
    # prefix + an 8/16-token suffix, so totals (56/64) pad to the SAME
    # 64-token prefill bucket — equal padded widths keep the cached
    # prefix KV bitwise identical to what each request's own prefill
    # writes, which is what makes sharing EXACT (1-ulp row-grouping
    # drift across buckets would make it merely close)
    rng = np_.random.default_rng(11)
    sys_prompt = rng.integers(0, VOCAB, size=48).tolist()
    N_REQ, GEN = 24, 16
    shared_trace = []
    t_arr = 0.0
    for i in range(N_REQ):
        sfx = rng.integers(0, VOCAB,
                           size=(8 if i % 2 else 16)).tolist()
        t_arr += float(rng.exponential(1e-5))   # saturating burst
        shared_trace.append({"arrival_t": t_arr,
                             "prompt": sys_prompt + sfx,
                             "max_new_tokens": GEN})

    def crc(engine, n):
        payload = b"".join(
            np_.asarray(engine.sequence(i).generated,
                        np_.int64).tobytes() for i in range(n))
        return zlib.crc32(payload) & 0xFFFFFFFF

    metrics.enable(metrics_dir, rank=0, flush_steps=1)

    # ---- run A: plain (no sharing, no speculation) — THE reference
    eng_a = make_engine()
    rep_a = simulate_serving(eng_a, [dict(r) for r in shared_trace])
    crc_a = crc(eng_a, N_REQ)
    truth = {i: list(eng_a.sequence(i).generated)
             for i in range(N_REQ)}

    # ---- run B: prefix caching only — the KV-bytes gate
    eng_b = make_engine(prefix=True)
    rep_b = simulate_serving(eng_b, [dict(r) for r in shared_trace])
    crc_b = crc(eng_b, N_REQ)
    kv_ratio = (rep_a.kv_bytes_per_request
                / max(rep_b.kv_bytes_per_request, 1.0))
    gates["prefix_kv_bytes_per_request_2x"] = kv_ratio >= 2.0
    gates["prefix_token_crc_equal"] = crc_b == crc_a
    log(f"serving-throughput prefix: KV/req "
        f"{rep_a.kv_bytes_per_request:,.0f}B -> "
        f"{rep_b.kv_bytes_per_request:,.0f}B ({kv_ratio:.2f}x, "
        f"gate >= 2) hits={rep_b.prefix_hits} "
        f"misses={rep_b.prefix_misses} crc_equal={crc_b == crc_a}")

    # ---- run C: prefix + speculation at a controlled 70% acceptance.
    # The oracle drafts from run A's token streams, choosing per round
    # how many leading drafts are TRUE so the running acceptance
    # tracks the setpoint; the wrong tail proves the verify pass
    # rejects without perturbing the stream.
    class OracleDrafter:
        def __init__(self, truth, k, target):
            self.truth, self.k, self.target = truth, k, target
            self.acc = 0
            self.prop = 0

        def __call__(self, seq):
            t = self.truth.get(seq.req_id)
            if t is None:
                return []
            done = len(seq.generated)
            room = seq.request.max_new_tokens - done
            k = min(self.k, room - 1)
            if k < 1 or done >= len(t):
                return []
            best_w, best_err = 0, None
            for w in range(k + 1):
                err = abs((self.acc + w) / (self.prop + k)
                          - self.target)
                if best_err is None or err < best_err:
                    best_w, best_err = w, err
            drafts = list(t[done:done + best_w])
            while len(drafts) < k:
                j = done + len(drafts)
                wrong = (t[j] + 1) % VOCAB if j < len(t) else 1
                drafts.append(int(wrong))
            self.acc += best_w
            self.prop += k
            return drafts

    drafter = OracleDrafter(truth, k=3, target=0.70)
    eng_c = make_engine(prefix=True, spec=SpeculativeConfig(
        num_draft_tokens=3, draft_fn=drafter))
    rep_c = simulate_serving(eng_c, [dict(r) for r in shared_trace])
    crc_c = crc(eng_c, N_REQ)
    gates["spec_token_crc_equal"] = crc_c == crc_a

    # ---- runs D/E: the THROUGHPUT half of the speculation gate on a
    # decode-bound workload (long generations, short prompts): a
    # decode step is dominated by the bytes every step streams
    # regardless of row count — on a chip, the weights — so a
    # (k+1)-row verify step emits ~1 + 0.7k tokens for little more
    # than a 1-row step's bytes (the flash-decode economics). WIDTH
    # CALIBRATION (PR 21): these two runs serve a hidden-512 model
    # (8 heads x 64), for the reason written out in bench_serving —
    # at gpt_tiny's hidden 64 the modeled per-row cost exceeds the
    # whole weight set, and the uplift only ever showed there because
    # the old decode program copied a layer out of the KV pool every
    # step. The engine batches TWO sequences (was four): speculation
    # is a small-batch tool — its (k+1) verify rows per sequence are
    # nearly free only while the step is bound by the weight stream,
    # and under the CPU-nominal rates of this clock (ridge 2 FLOP/byte)
    # an f32 step turns compute-bound past ~8 rows, where every verify
    # row costs a full row. The saturating shared trace above stays
    # the EXACTNESS half (crc_c).
    wide = GPTForCausalLM(gpt_tiny(
        use_scan=False, hidden_size=512, num_heads=8,
        max_position_embeddings=128))
    N_D, GEN_D = 12, 48
    spec_trace = []
    t_arr = 0.0
    for i in range(N_D):
        t_arr += float(rng.exponential(1e-6))
        spec_trace.append({
            "arrival_t": t_arr,
            "prompt": rng.integers(0, VOCAB, size=16).tolist(),
            "max_new_tokens": GEN_D})

    def make_decode_engine(spec=None):
        return ServingEngine(wide, config=EngineConfig(
            block_size=16, num_blocks=128, max_batch=2,
            prefill_budget_tokens=128, max_model_len=128, spec=spec))

    eng_d = make_decode_engine()
    rep_d = simulate_serving(eng_d, [dict(r) for r in spec_trace])
    crc_d = crc(eng_d, N_D)
    truth_d = {i: list(eng_d.sequence(i).generated)
               for i in range(N_D)}
    drafter_d = OracleDrafter(truth_d, k=3, target=0.70)
    eng_e = make_decode_engine(spec=SpeculativeConfig(
        num_draft_tokens=3, draft_fn=drafter_d))
    rep_e = simulate_serving(eng_e, [dict(r) for r in spec_trace])
    crc_e = crc(eng_e, N_D)
    uplift = rep_e.tokens_per_s / max(rep_d.tokens_per_s, 1e-12)
    gates["spec_decode_trace_crc_equal"] = crc_e == crc_d
    gates["spec_tokens_per_s_uplift_1p5x"] = uplift >= 1.5
    gates["spec_acceptance_at_setpoint"] = (
        rep_e.spec_rejected > 0
        and abs(rep_e.spec_acceptance - 0.70) <= 0.02)
    log(f"serving-throughput spec: {rep_d.tokens_per_s:,.0f} -> "
        f"{rep_e.tokens_per_s:,.0f} modeled tok/s ({uplift:.2f}x, "
        f"gate >= 1.5) acceptance={rep_e.spec_acceptance:.3f} "
        f"(accepted={rep_e.spec_accepted} "
        f"rejected={rep_e.spec_rejected}) steps {rep_d.decode_steps}"
        f"->{rep_e.decode_steps} combined-crc_equal={crc_c == crc_a}")

    metrics.flush()
    metrics.export_prometheus()
    metrics.disable()

    # doctors see the new economics: raw counters in perf_doctor,
    # derived rates in serve_doctor's THROUGHPUT section
    pd_rep = perf_doctor.summarize(perf_doctor.load_streams(metrics_dir),
                                   warmup=0)
    cnt = pd_rep.get("counters") or {}
    thr = serve_doctor.load_throughput(metrics_dir)
    # the metrics window covered runs B..E: the joined ledgers must
    # reproduce the sim reports' own counts exactly
    acc_all = rep_c.spec_accepted + rep_e.spec_accepted
    rej_all = rep_c.spec_rejected + rep_e.spec_rejected
    gates["doctors_surface_economics"] = (
        cnt.get("serving_prefix_hits_total", 0) > 0
        and cnt.get("serving_spec_accepted_total", 0) == acc_all > 0
        and thr["spec_acceptance"] is not None
        and abs(thr["spec_acceptance"]
                - acc_all / max(acc_all + rej_all, 1)) < 1e-9
        and thr["prefix_hit_rate"] is not None)

    # ---- 32k-context kernel gate (pinned v5e rates — deterministic
    # on every host; the PR 9 body has no latency to model at 32k)
    PEAK, HBMBW = 197e12, 819e9
    CTX32K, H32, D32 = 32768, 16, 128
    m_old = pa.modeled_decode_latency_s(
        CTX32K, num_heads=H32, head_dim=D32, dtype="bfloat16",
        peak_flops=PEAK, hbm_bps=HBMBW)
    pps_auto = pa.auto_pages_per_split(
        -(-CTX32K // 16), 16, D32, "bfloat16")
    m_new = pa.modeled_decode_latency_s(
        CTX32K, num_heads=H32, head_dim=D32, dtype="bfloat16",
        pages_per_split=pps_auto, peak_flops=PEAK, hbm_bps=HBMBW)
    ideal_s = m_new["kv_bytes"] / HBMBW
    gates["kernel_32k_single_softmax_infeasible"] = \
        not m_old["feasible"]
    gates["kernel_32k_split_feasible_near_roofline"] = (
        m_new["feasible"] and m_new["n_splits"] > 1
        and m_new["latency_s"] <= 1.25 * ideal_s)
    # executed evidence at a multi-split context (fast on CPU)
    krng = np_.random.default_rng(5)
    bs_k, Hk, Dk, ctx_k = 16, 2, 16, 160        # 10 pages
    n_pg = -(-ctx_k // bs_k)
    kq = krng.normal(size=(1, 1, Hk, Dk)).astype(np_.float32)
    # one layer's pool, a token's heads merged into one row:
    # [N, bs, H*D] (the kernel takes the whole model's, [L, ...])
    kp = krng.normal(size=(24, bs_k, Hk * Dk)).astype(np_.float32)
    vp = krng.normal(size=(24, bs_k, Hk * Dk)).astype(np_.float32)
    tb = krng.permutation(np_.arange(1, 24))[:n_pg][None, :] \
        .astype(np_.int32)
    o_split = paged_attention_decode(
        jnp.asarray(kq), jnp.asarray(kp)[None], jnp.asarray(vp)[None], tb,
        np_.asarray([ctx_k]), pages_per_split=3)
    r_split = paged_attention_split_reference(
        jnp.asarray(kq), jnp.asarray(kp), jnp.asarray(vp), tb,
        np_.asarray([ctx_k]), pages_per_split=3)
    r_glob = paged_attention_reference(
        jnp.asarray(kq), jnp.asarray(kp), jnp.asarray(vp), tb,
        np_.asarray([ctx_k]))
    # the kernel sums per page then across pages, its mirror per row:
    # a few ulp in fp32, not bitwise (tests/test_serving.py KERNEL_TOL)
    gates["kernel_split_matches_mirror"] = bool(np_.allclose(
        np_.asarray(o_split), np_.asarray(r_split),
        rtol=2e-6, atol=2e-6))
    gates["kernel_split_allclose_vs_global"] = bool(np_.allclose(
        np_.asarray(o_split), np_.asarray(r_glob),
        rtol=2e-6, atol=2e-6))
    log(f"serving-throughput 32k: single-softmax scratch "
        f"{m_old['scratch_vmem_bytes']/2**20:.1f}MiB infeasible="
        f"{not m_old['feasible']}; split pps={pps_auto} "
        f"({m_new['n_splits']} splits, "
        f"{m_new['scratch_vmem_bytes']/2**20:.1f}MiB) modeled "
        f"{m_new['latency_s']*1e3:.3f}ms <= 1.25x roofline "
        f"{ideal_s*1e3:.3f}ms")

    # ---- int4 weight-only: bound holds + non-vacuous (ROADMAP 4)
    qrng = np_.random.default_rng(7)
    xq = jnp.asarray(qrng.normal(size=(32, 64)), jnp.float32)
    wq = jnp.asarray(qrng.normal(size=(64, 128)), jnp.float32)
    w_i4, s4 = pm.quantize_channelwise(wq, 4, axis=1)
    packed = pm.pack_int4(w_i4)
    y4 = pm.int4_weight_only_matmul(xq, packed, s4)
    x64 = np_.asarray(xq, np_.float64)
    w64 = np_.asarray(wq, np_.float64)
    y_ref = x64 @ w64
    bound4 = np_.asarray(pm.weight_quant_error_bound(xq, s4, 4),
                         np_.float64)
    err4 = np_.abs(np_.asarray(y4, np_.float64) - y_ref)
    holds = bool((err4 <= bound4 + 1e-6).all())
    w_i2, s2 = pm.quantize_channelwise(wq, 2, axis=1)
    y2 = pm.int8_weight_only_matmul(xq, w_i2, s2, quant_bits=2)
    err2 = np_.abs(np_.asarray(y2, np_.float64) - y_ref)
    violated = bool((err2 > bound4).any())
    informative = bool(bound4.max() < np_.abs(y_ref).max())
    gates["int4_bound_holds"] = holds
    gates["int4_bound_nonvacuous"] = violated and informative
    log(f"serving-throughput int4: bound holds={holds} (max err "
        f"{err4.max():.4f} <= max bound {bound4.max():.4f}); 2-bit "
        f"payload violates={violated}; informative={informative}")

    # ---- PR 11/12 composition: the four reliability drills with
    # prefix caching + speculation ENABLED (n-gram self-draft — the
    # drill traces use a narrow token range so drafts actually fire)
    probe = make_engine()
    simulate_serving(probe, poisson_trace(
        2, rate_per_s=100.0, prompt_lens=[16, 24],
        gen_tokens=[12, 24], vocab=VOCAB, seed=1))
    b1_key = min(probe.runner._decode_costs)
    decode_s = cost_seconds(probe.runner.decode_cost(b1_key))
    probe_interval_s = 2.0 * decode_s
    base_capacity = 1.0 / decode_s
    mean_gen = 18.0

    def drill_trace(n, seed, rate, priorities=False):
        t = poisson_trace(n, rate_per_s=rate, prompt_lens=[16, 24],
                          gen_tokens=[12, 24], vocab=8, seed=seed)
        if priorities:
            for i, r in enumerate(t):
                r["priority"] = 1 if i % 3 == 0 else 0
        return t

    def run_drill(name, n_engines, rel=None, arm=None, n=16, seed=101,
                  rate=None, priorities=False, on_round=None,
                  features=True):
        rate = rate if rate is not None else \
            2.0 * base_capacity / mean_gen
        tdir = os.path.join(trace_root, name)
        shutil.rmtree(tdir, ignore_errors=True)
        tracing.enable(tdir, rank=0)
        if arm:
            chaos.arm(arm)
        spec = SpeculativeConfig(num_draft_tokens=3) if features \
            else None
        router = EngineFailoverRouter(
            [make_engine(prefix=features, spec=spec, reliability=rel,
                         num_blocks=40) for _ in range(n_engines)],
            probe_interval_s=probe_interval_s)
        rep = simulate_router(
            router,
            [dict(r) for r in drill_trace(n, seed, rate, priorities)],
            on_round=on_round)
        # fired set read BEFORE disarm (disarm drops the injector and
        # its ledger with it)
        fired = {k for k, _ in chaos.fired_log()}
        chaos.disarm()
        tracing.flush()
        tracing.disable()
        return router, rep, tdir, fired

    def router_crc(router, rep):
        payload = b"".join(
            np_.asarray(router.sequence(r).generated,
                        np_.int64).tobytes() for r in rep.rids)
        return zlib.crc32(payload) & 0xFFFFFFFF

    def decomp_exact(tdir, rep):
        dec = tracing.decompose(tracing.load_trace_dir(tdir))
        fin = {t: c for t, c in dec.items() if c["finished"]}
        return (len(fin) == rep.completed
                and all(c["exact"] for c in fin.values()), len(fin))

    # drill 1: engine kill -> failover, token-for-token vs clean twin
    r_clean, rep_clean, d_clean, _ = run_drill("kill_clean", 2)
    r_kill, rep_kill, d_kill, _ = run_drill("kill", 2,
                                            arm="kill_engine:4:1")
    ok_kill, fin_kill = decomp_exact(d_kill, rep_kill)
    gates["compose_kill_token_for_token"] = (
        rep_kill.completed == rep_clean.completed == 16
        and router_crc(r_kill, rep_kill)
        == router_crc(r_clean, rep_clean)
        and rep_kill.failovers == 1)
    gates["compose_kill_decomposition_exact"] = ok_kill
    # drill 2: transient faults (drop + corrupt) token-invisible, and
    # the allocator + prefix-cache ledger closes: every non-cached
    # block back on the free list, every cached block held ONLY by
    # the cache
    r1_clean, rep1_clean, _, _ = run_drill("tr_clean", 1)
    r_tr, rep_tr, d_tr, fired = run_drill(
        "transient", 1, arm="drop_decode_step:3,corrupt_block_table:5:1")
    eng_tr = r_tr.engines[0]
    cache_tr = eng_tr.prefix_cache
    ok_tr, _ = decomp_exact(d_tr, rep_tr)
    gates["compose_transient_token_invisible"] = (
        fired == {"drop_decode_step", "corrupt_block_table"}
        and rep_tr.completed == 16
        and router_crc(r_tr, rep_tr)
        == router_crc(r1_clean, rep1_clean))
    gates["compose_transient_ledger_closes"] = (
        eng_tr.allocator.free_count + len(cache_tr.held_blocks())
        == eng_tr.allocator.num_blocks - 1
        and all(eng_tr.allocator.refcount(b) == 1
                for b in cache_tr.held_blocks()))
    gates["compose_transient_decomposition_exact"] = ok_tr
    # drill 3: overload burst vs bounded queue + priorities
    r_over, rep_over, d_over, _ = run_drill(
        "overload", 1, rel=ReliabilityConfig(max_queue_depth=6),
        n=40, seed=202, rate=10.0 * base_capacity / mean_gen,
        priorities=True)
    shed_prios = [s.priority for s in r_over.engines[0].scheduler.shed]
    shed_n = rep_over.shed + rep_over.rejected
    ok_over, _ = decomp_exact(d_over, rep_over)
    gates["compose_overload_sheds_lowest_only"] = (
        0 < shed_n <= 24 and all(p == 0 for p in shed_prios)
        and rep_over.completed == rep_over.submitted - rep_over.shed)
    gates["compose_overload_decomposition_exact"] = ok_over
    # drill 4: staged hot-swap + rollback, census vs no-swap twin
    r_ref, rep_ref, _, _ = run_drill("swap_ref", 2, n=16, seed=303)
    census_ref = [e.num_decode_programs for e in r_ref.engines]
    swap_state = {}

    def on_round(rt, clock, idx):
        ctl = swap_state.get("ctl")
        if ctl is None:
            new_w = [w * 1.001
                     if "float" in str(getattr(w, "dtype", "")) else w
                     for w in rt.engines[0].runner._weights()]
            ctl = swap_state["ctl"] = HotSwapController(
                rt.engines, new_w)
        if idx in (6, 9):
            ctl.stage_next(now=clock)
        elif idx == 14 and ctl.state == "committed":
            ctl.rollback(now=clock)

    r_swap, rep_swap, d_swap, _ = run_drill("swap", 2, n=16, seed=303,
                                            on_round=on_round)
    census_swap = [e.num_decode_programs for e in r_swap.engines]
    ctl = swap_state["ctl"]
    ok_swap, _ = decomp_exact(d_swap, rep_swap)
    gates["compose_hot_swap_zero_dropped_census"] = (
        rep_swap.completed == 16 and ctl.state == "rolled_back"
        and census_swap == census_ref)
    gates["compose_hot_swap_decomposition_exact"] = ok_swap
    log(f"serving-throughput compose: kill crc_eq="
        f"{gates['compose_kill_token_for_token']} transient_ok="
        f"{gates['compose_transient_token_invisible']} overload shed="
        f"{shed_n} swap census {census_swap} vs {census_ref}; "
        f"decomposition exact on all four drills="
        f"{ok_kill and ok_tr and ok_over and ok_swap}")

    result = {
        "metric": "serving_throughput_next_tier",
        "value": round(uplift, 3),
        "unit": "x modeled tokens/s at 70% acceptance "
                "(prefix+spec vs plain)",
        "prefix": {
            "kv_bytes_per_request_plain":
                round(rep_a.kv_bytes_per_request, 1),
            "kv_bytes_per_request_shared":
                round(rep_b.kv_bytes_per_request, 1),
            "kv_reduction_x": round(kv_ratio, 3),
            "hits": rep_b.prefix_hits,
            "misses": rep_b.prefix_misses,
            "tokens_crc": crc_b,
        },
        "speculation": {
            "tokens_per_s_plain": round(rep_d.tokens_per_s, 1),
            "tokens_per_s_spec": round(rep_e.tokens_per_s, 1),
            "uplift_x": round(uplift, 3),
            "acceptance": round(rep_e.spec_acceptance, 4),
            "accepted": rep_e.spec_accepted,
            "rejected": rep_e.spec_rejected,
            "decode_steps_plain": rep_d.decode_steps,
            "decode_steps_spec": rep_e.decode_steps,
            "decode_trace_tokens_crc": crc_e,
            "combined_tokens_crc": crc_c,
        },
        "reference_tokens_crc": crc_a,
        "kernel_32k": {
            "single_softmax_scratch_mib":
                round(m_old["scratch_vmem_bytes"] / 2 ** 20, 2),
            "single_softmax_feasible": m_old["feasible"],
            "split_pages_per_split": pps_auto,
            "split_n_splits": m_new["n_splits"],
            "split_scratch_mib":
                round(m_new["scratch_vmem_bytes"] / 2 ** 20, 2),
            "split_modeled_latency_ms":
                round(m_new["latency_s"] * 1e3, 4),
            "kv_roofline_ms": round(ideal_s * 1e3, 4),
        },
        "int4": {
            "max_err": round(float(err4.max()), 6),
            "max_bound": round(float(bound4.max()), 6),
            "two_bit_violates": violated,
        },
        "compose": {
            "kill_completed": rep_kill.completed,
            "kill_failovers": rep_kill.failovers,
            "transient_completed": rep_tr.completed,
            "overload_shed": shed_n,
            "swap_census": census_swap,
            "decomposed_finished": fin_kill,
        },
        "gates": gates,
    }
    return emit_result("serving-throughput",
                       "SERVING_THROUGHPUT_r01.json", result)


def bench_single_chip_speed():
    """``--single-chip-speed``: the raw-speed gate for ROADMAP item 3.
    Ported byte-for-byte onto the ``bench/scenarios/`` registry lane.
    Drill, gates, artifact (``SPEED_r01.json``) and stdout JSON line
    unchanged; see ``bench/scenarios/single_chip_speed.py``."""
    from bench.scenarios import run_scenario
    return run_scenario("single-chip-speed")


def main():
    if "--tracing" in sys.argv:
        sys.exit(bench_tracing())
    if "--single-chip-speed" in sys.argv:
        sys.exit(bench_single_chip_speed())
    if "--serving-throughput" in sys.argv:
        sys.exit(bench_serving_throughput())
    if "--serving-reliability" in sys.argv:
        sys.exit(bench_serving_reliability())
    if "--fleet-kv" in sys.argv:
        sys.exit(bench_fleet_kv())
    if "--million-user-day" in sys.argv:
        sys.exit(bench_million_user_day())
    if "--ps-recommender" in sys.argv:
        sys.exit(bench_ps_recommender())
    if "--moe-training" in sys.argv:
        sys.exit(bench_moe_training())
    if "--long-context" in sys.argv:
        sys.exit(bench_long_context())
    if "--serving" in sys.argv:
        sys.exit(bench_serving())
    if "--multichip-scaling" in sys.argv:
        sys.exit(bench_multichip_scaling())
    if "--inject-fault" in sys.argv:
        sys.exit(bench_fault_tolerance())
    if "--guardrails" in sys.argv:
        sys.exit(bench_guardrails())
    if "--flight-recorder" in sys.argv:
        sys.exit(bench_flight_recorder())
    if "--sdc" in sys.argv:
        sys.exit(bench_sdc())
    if "--reliable-step" in sys.argv:
        sys.exit(bench_reliable_step())
    if "--observability" in sys.argv:
        sys.exit(bench_observability())
    if "--elastic" in sys.argv:
        sys.exit(bench_elastic())
    mode = os.environ.get("BENCH_MODEL", "gpt")
    if mode in ("scaling", "gpt_hybrid", "zero3"):
        # must run BEFORE anything imports jax: the device-count env var
        # is read at backend init
        return {"scaling": bench_scaling,
                "gpt_hybrid": bench_gpt_hybrid,
                "zero3": bench_zero3}[mode]()
    if os.environ.get("BENCH_AUTOTUNE", "0") == "1":
        from paddle2_tpu.incubate import autotune
        autotune.set_config({"kernel": {"enable": True}})
    if os.environ.get("BENCH_FLASH", "1") == "0":
        from paddle2_tpu.kernels.attention import set_flash_enabled
        set_flash_enabled(False)
    {"gpt": bench_gpt, "ernie": bench_ernie,
     "resnet50": bench_resnet50}[mode]()


if __name__ == "__main__":
    main()
