"""The drill registry's command line, and one chip lane.

  python bench.py --list     a line per drill: name, artifact, kind, streams
  python bench.py --<name>   run that drill of ``bench/scenarios/``: one JSON
                             line on stdout, the artifact under
                             ``bench/artifacts/``, exit code = gate verdict
  python bench.py            ``bench_resnet50`` on the chip (refuses without)

The drills are modeled (cost x rate on a virtual clock) or host-side
smokes: none of their figures is a device metric. How fast the system
is on the chip is ``benchmark/`` + ``BENCHMARK.json``; read PERF.md.
"""

import json
import os
import sys
import time

import numpy as np

from bench.artifact import artifact_path, log
from bench.scenarios import REGISTRY, run


def _require_chip(lane: str) -> None:
    """A device lane measures a chip: without one it refuses to run
    instead of shrinking to a CPU profile under a device metric's name."""
    import jax
    d = jax.devices()[0]
    if d.platform.lower() != "tpu":
        raise SystemExit(
            f"bench.py {lane}: this lane measures a TPU chip and JAX "
            f"reports platform {d.platform!r} — no figure is printed. "
            "Run it on the chip; the drills (python bench.py --list) "
            "are the CPU ones.")


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


def bench_resnet50():
    """ResNet-50 ImageNet training step on the chip: kept until the
    benchmark has a convolution cell (ROADMAP W6)."""
    import jax
    import paddle2_tpu as paddle
    import paddle2_tpu.optimizer as opt
    from paddle2_tpu.observability.cost_model import chip_peak
    import paddle2_tpu.nn.functional as F
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

    def train_fn(img, labels):
        logits = model(img)
        return F.cross_entropy(logits.astype("float32"), labels)

    rs = np.random.RandomState(0)

    def mk(i):
        return (paddle.to_tensor(
            (rs.randn(batch, 3, size, size) * 0.5).astype(np.float32))
            .astype("bfloat16"),
            paddle.to_tensor(
                rs.randint(0, 1000, (batch,)).astype(np.int32)))
    next_batch = _batch_cycler(mk, n=8)
    step = paddle.jit.train_step(train_fn, o)

    def one_step():
        img, lbl = next_batch()
        return step(img, lbl)

    dt, loss = _run_steps(one_step, steps)
    ips = batch / dt
    fwd_flops = 4.1e9    # per image, ResNet-50 at 224
    model_flops = ips * 3 * fwd_flops
    peak, _, chip = chip_peak()
    print(json.dumps({
        "metric": "resnet50_imagenet_images_per_sec",
        "value": round(ips, 1),
        "unit": "images/s",
        "step_time_s": round(dt, 4),
        "mfu": round(model_flops / peak, 3),
        "chip": chip,
        "model_params_m": round(n_params / 1e6, 1),
        "config": {"batch": batch, "image": size},
        "device": str(jax.devices()[0]),
        "loss": float(np.asarray(loss._data)),
    }))


def main(argv):
    flags = [a[2:] for a in argv if a.startswith("--")]
    if "list" in flags:
        for sc in REGISTRY.values():
            print(sc.name,
                  os.path.relpath(artifact_path(sc.artifact))
                  if sc.artifact else "-",
                  "deterministic" if sc.deterministic else "host-clock",
                  ",".join(sc.streams.values()) or "-")
        return 0
    if not flags:
        return bench_resnet50()
    names = [f for f in flags if f in REGISTRY]
    if not names:
        raise SystemExit(f"bench.py: no drill among {flags}; "
                         f"registered: {sorted(REGISTRY)}")
    return run(names[0])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
