"""paddle.incubate.autotune (reference python/paddle/incubate/autotune.py
set_config: kernel / layout / dataloader tuning).

TPU-native content: "kernel" tuning picks Pallas flash-attention block
sizes per attention shape and caches the winner (the analog of the
reference's cuDNN algo exhaustive search); "layout" is a no-op (XLA owns
layouts on TPU); "dataloader" tuning probes worker counts.

Two additions beyond the reference surface:

* **Deterministic kernel scoring** — candidate block sizes can be
  scored by an analytic VMEM-traffic/compute model instead of a wall
  clock. This is the DEFAULT on CPU (CI, dryrun parity: wall clocks in
  shared sandboxes pick a different winner every run, which changes
  the compiled program under test) and opt-in everywhere via
  ``PADDLE_AUTOTUNE_MODE=model``. Exact score ties break through a
  seeded RNG (``PADDLE_AUTOTUNE_SEED``), so the tuned blocks are
  reproducible run to run AND the tie-break policy is explicit.
* **Remat policy search** (:func:`search_remat_policy`) — enumerates
  ``jax.checkpoint`` policies for a GPT block (save-everything /
  save-dots(+qkv/mlp/ln variants) / save-nothing / host-offload),
  scores each candidate by the deterministic cost model (recompute
  FLOPs added + HBM bytes re-touched vs activation bytes saved
  against an explicit memory budget), and picks the minimal-recompute
  policy that fits. The winner wires into ``models/gpt.py``
  (``recompute_granularity="search"``), ``jit/train_step.py`` (the
  resolved policy keys the program cache), and
  ``distributed/recompute.py`` (``policy=`` pass-through) — see the
  README "Raw speed" section.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_config = {"kernel": {"enable": False, "tuning_range": [1, 10]},
           "layout": {"enable": False},
           "dataloader": {"enable": False}}

_block_cache: Dict[Tuple, Tuple[int, int]] = {}
_CANDIDATES = ((256, 256), (256, 512), (512, 512), (512, 1024),
               (1024, 1024))

AUTOTUNE_MODE_ENV = "PADDLE_AUTOTUNE_MODE"      # "model" | "measure"
AUTOTUNE_SEED_ENV = "PADDLE_AUTOTUNE_SEED"


def set_config(config=None):
    """incubate/autotune.py:23 parity: dict or json file path."""
    if config is None:
        for v in _config.values():
            v["enable"] = True
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    for k, v in config.items():
        if k in _config and isinstance(v, dict):
            _config[k].update(v)


def kernel_tuning_enabled() -> bool:
    return bool(_config["kernel"]["enable"])


def autotune_mode() -> str:
    """``"model"`` (deterministic cost-model scoring) or ``"measure"``
    (wall-clock A/B). Default: ``model`` off-accelerator — CI and the
    virtual-device parity suites must compile the SAME program every
    run — ``measure`` on real TPUs, env-overridable either way."""
    env = os.environ.get(AUTOTUNE_MODE_ENV, "").strip().lower()
    if env in ("model", "measure"):
        return env
    from ..kernels._platform import on_tpu
    return "measure" if on_tpu() else "model"


def _tie_rng():
    import numpy as np
    return np.random.RandomState(
        int(os.environ.get(AUTOTUNE_SEED_ENV, "0")))


def _model_flash_block_score(q_shape, k_shape, causal: bool,
                             bq: int, bk: int) -> float:
    """Analytic per-candidate cost of one flash-attention pass:
    HBM traffic (K/V re-streamed once per q-block) + a fixed per-tile
    dispatch overhead, in nominal seconds under the observability rate
    model. Pure function of (shapes, blocks) — no wall clock."""
    from ..observability.cost_model import chip_peak
    peak, hbm, _ = chip_peak()
    b, sq = q_shape[0], q_shape[1]
    sk = k_shape[1]
    hd = 1
    for d in q_shape[2:]:
        hd *= d
    n_q = -(-sq // bq)
    n_k = -(-sk // bk)
    tiles = n_q * n_k
    if causal and sq == sk:
        tiles = (n_q * (n_k + 1)) // 2      # lower-triangular tile set
    bytes_io = 2.0 * b * hd * (sq + n_q * sk * 2)   # q once, k/v per row
    flops = 4.0 * b * sq * sk * hd * (0.5 if causal and sq == sk else 1.0)
    per_tile_overhead = 2e-7                # grid dispatch + pipeline fill
    return flops / peak + bytes_io / hbm + tiles * per_tile_overhead


def best_flash_blocks(q_shape, k_shape, causal: bool,
                      default: Tuple[int, int]) -> Tuple[int, int]:
    """Block-size search, cached per (shapes, causal, mode).

    ``model`` mode scores candidates with the deterministic analytic
    model above; ``measure`` mode times them (TPU only — wall clock).
    Both modes break exact ties with the seeded RNG so the tuned
    blocks are reproducible."""
    mode = autotune_mode()
    key = (tuple(q_shape), tuple(k_shape), bool(causal), mode)
    hit = _block_cache.get(key)
    if hit is not None:
        return hit
    from ..kernels import pallas_flash as pf
    viable = [(bq, bk) for bq, bk in _CANDIDATES
              if pf.supported(q_shape, k_shape, bq, bk)]
    if not viable:
        _block_cache[key] = default
        return default
    if mode == "model":
        scores = [(_model_flash_block_score(q_shape, k_shape, causal,
                                            bq, bk), (bq, bk))
                  for bq, bk in viable]
        best_score = min(s for s, _ in scores)
        tied = [c for s, c in scores if s == best_score]
        best = tied[0] if len(tied) == 1 else \
            tied[_tie_rng().randint(len(tied))]
        _block_cache[key] = best
        return best
    import jax
    import jax.numpy as jnp
    import numpy as np
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(*q_shape), jnp.bfloat16)
    k = jnp.asarray(rs.randn(*k_shape), jnp.bfloat16)
    best, best_t = default, float("inf")
    measured = []
    for bq, bk in viable:
        try:
            f = jax.jit(lambda a, b, c, _bq=bq, _bk=bk:
                        pf.flash_attention_bshd(a, b, c, causal=causal,
                                                block_q=_bq, block_k=_bk))
            o = f(q, k, k)
            _ = float(jnp.sum(o.astype(jnp.float32)))  # true sync
            t0 = time.perf_counter()
            for _i in range(3):
                o = f(o, k, k)
            _ = float(jnp.sum(o.astype(jnp.float32)))
            dt = time.perf_counter() - t0
            measured.append((dt, (bq, bk)))
            if dt < best_t:
                best, best_t = (bq, bk), dt
        except Exception:
            continue
    tied = [c for dt, c in measured if dt == best_t]
    if len(tied) > 1:
        best = tied[_tie_rng().randint(len(tied))]
    _block_cache[key] = best
    return best


# ===================================================================
# Remat policy search (cost-model-guided jax.checkpoint selection)
# ===================================================================

# elementwise recompute cost, FLOPs per element (nominal VPU op counts;
# only RELATIVE weight matters — every candidate is scored by the same
# table)
_LN_FLOPS_PER_ELEM = 8.0        # two reduction passes + normalize+affine
_GELU_FLOPS_PER_ELEM = 12.0     # tanh-approx gelu
_ADD_FLOPS_PER_ELEM = 1.0

# host-offload link rate is owned by cost_model (shared with the
# serving KV spill tier — same channel, one owner, no drift); the old
# local names stay as aliases for compatibility.
from ..observability.cost_model import (
    HOST_ENV as OFFLOAD_ENV,
    DEFAULT_HOST_GBPS as _DEFAULT_OFFLOAD_GBPS,
    host_link_bps as _host_link_bps,
)


@dataclass
class RematCandidate:
    """One remat policy's per-layer accounting at a given (batch, seq).

    ``granularity`` is the ``GPTConfig.recompute_granularity`` value
    the candidate wires to (``None`` = no ``jax.checkpoint`` at all).
    ``saved_bytes`` is the activation HBM held per layer for backward;
    ``recompute_flops``/``recompute_bytes`` the extra work backward
    pays; ``offload_bytes`` what leaves HBM for pinned host memory
    (charged at the offload link, twice: out in forward, back in
    backward)."""
    name: str
    granularity: Optional[str]
    saved_bytes: float
    recompute_flops: float
    recompute_bytes: float
    offload_bytes: float = 0.0
    wired: bool = True      # False: excluded from the search

    def overhead_s(self, peak_flops: float, hbm_bps: float,
                   offload_bps: float) -> float:
        """Modeled backward-overhead seconds per layer — the score."""
        return (self.recompute_flops / peak_flops
                + self.recompute_bytes / hbm_bps
                + 2.0 * self.offload_bytes / offload_bps)


@dataclass
class RematPlan:
    """The searcher's verdict: the chosen policy plus the full scored
    table (the bench prints it; the budget gate re-checks it)."""
    policy: str
    granularity: Optional[str]
    use_recompute: bool
    fits: bool
    budget_bytes: float
    fixed_bytes: float
    activation_bytes: float     # L x saved_bytes of the chosen policy
    total_bytes: float
    recompute_flops: float      # L x per-layer, chosen policy
    overhead_s: float           # L x per-layer modeled seconds
    table: List[Dict] = field(default_factory=list)

    def cache_token(self) -> Tuple:
        """Hashable token for the jit.train_step program cache: two
        models differing only in searched policy must not share a
        compiled entry."""
        return ("remat", self.policy, self.granularity,
                self.use_recompute)


def gpt_remat_candidates(hidden: int, ffn: int, num_heads: int,
                         tokens: int, act_bytes: int = 2
                         ) -> List[RematCandidate]:
    """The per-layer accounting table for one GPT pre-LN block at
    ``tokens = batch x seq`` activations of ``act_bytes`` each.

    Saved-tensor census per policy (t = tokens, H = hidden, F = ffn):

    ===================  ==========================================
    save_all             every intermediate: ln1/ln2 (2H), qkv (3H),
                         flash o (H) + f32 lse, out_proj (H), both
                         residuals (2H), up (F), gelu (F), down (H)
    save_dots_plus_ln    dots + gelu + both LN outputs
    save_dots_plus       dots + gelu output   (the "save-qkv-and-mlp-
                         activations" point: every matmul input in
                         backward is materialized)
    save_dots            matmul outputs + pinned flash (o, lse) only
    save_nothing         block input only; backward re-runs the whole
                         forward (matmul FLOPs included)
    save_all_offload     save_all's tensors, parked in pinned host
                         memory — HBM cost of save_nothing, transfer
                         cost of the full activation set
    ===================  ==========================================
    """
    t, H, F, N = float(tokens), float(hidden), float(ffn), float(num_heads)
    a = float(act_bytes)
    lse = t * N * 4.0                       # f32, per layer
    dots = t * (7.0 * H + F) * a + lse      # in + qkv + o + proj + up+down
    all_saved = t * (10.0 * H + 2.0 * F) * a + lse
    ln_flops = 2.0 * _LN_FLOPS_PER_ELEM * t * H          # ln1 + ln2
    gelu_flops = _GELU_FLOPS_PER_ELEM * t * F
    add_flops = 2.0 * _ADD_FLOPS_PER_ELEM * t * H
    cands = [
        RematCandidate("save_all", None, all_saved, 0.0, 0.0),
        RematCandidate(
            "save_dots_plus_ln", "dots_plus_ln",
            dots + t * (2.0 * H + F) * a,
            add_flops, 2.0 * t * H * a),
        RematCandidate(
            "save_dots_plus", "dots_plus",
            dots + t * F * a,
            ln_flops + add_flops, t * (6.0 * H) * a),
        RematCandidate(
            "save_dots", "dots", dots,
            ln_flops + gelu_flops + add_flops,
            t * (6.0 * H + 2.0 * F) * a),
    ]
    return cands


def search_remat_policy(*, hidden: int, num_layers: int, num_heads: int,
                        seq: int, batch: int,
                        ffn: Optional[int] = None,
                        budget_bytes: float,
                        fixed_bytes: float = 0.0,
                        act_bytes: int = 2,
                        peak_flops: Optional[float] = None,
                        hbm_bps: Optional[float] = None,
                        offload_gbps: Optional[float] = None,
                        allow_offload: bool = True) -> RematPlan:
    """Deterministic remat policy search for a GPT block stack.

    Enumerates the candidate table, keeps the candidates whose total
    footprint (``fixed_bytes`` — params/grads/optimizer state — plus
    ``num_layers x saved_bytes``) fits ``budget_bytes``, and returns
    the one with the LOWEST modeled backward overhead (recompute FLOPs
    at the chip peak + re-touched HBM bytes at the HBM rate + offload
    traffic at the host link). Exact-score ties break through the
    seeded autotune RNG. When nothing fits, ``save_nothing`` is
    returned with ``fits=False`` — minimal memory is the only honest
    fallback, and the caller (bench gate / README) surfaces it.

    Pure function of its arguments + the rate model: the same config
    resolves to the same policy on every host, so the compiled train
    step is reproducible (the plan's :meth:`~RematPlan.cache_token`
    keys the program cache)."""
    from ..observability.cost_model import chip_peak
    if peak_flops is None or hbm_bps is None:
        p, h, _ = chip_peak()
        peak_flops = peak_flops if peak_flops is not None else p
        hbm_bps = hbm_bps if hbm_bps is not None else h
    offload_bps = _host_link_bps(offload_gbps)
    F = int(ffn if ffn is not None else 4 * hidden)
    tokens = int(batch) * int(seq)
    t, H = float(tokens), float(hidden)
    a = float(act_bytes)
    cands = gpt_remat_candidates(hidden, F, num_heads, tokens, act_bytes)
    # save_nothing: full forward re-run in backward (matmul FLOPs are
    # geometry-dependent — built here where seq is known)
    mm_flops = 2.0 * t * H * (4.0 * H + 2.0 * F) + 4.0 * t * seq * H
    ew_flops = (2.0 * _LN_FLOPS_PER_ELEM * t * H
                + _GELU_FLOPS_PER_ELEM * t * F
                + 2.0 * _ADD_FLOPS_PER_ELEM * t * H)
    cands.append(RematCandidate(
        "save_nothing", "full", t * H * a,
        mm_flops + ew_flops, t * (10.0 * H + 2.0 * F) * a))
    if allow_offload:
        # offload variant: HBM footprint of save_nothing, backward
        # work of save_dots — the dot outputs are parked in pinned
        # host memory (and charged twice on the host link) instead of
        # recomputed or held in HBM
        dots_c = next(c for c in cands if c.name == "save_dots")
        cands.append(RematCandidate(
            "offload_dots", "offload", t * H * a,
            dots_c.recompute_flops, dots_c.recompute_bytes,
            offload_bytes=dots_c.saved_bytes))
    # residual stream between layers rides on top of every policy
    residual = t * H * a
    L = int(num_layers)
    rows: List[Dict] = []
    fitting: List[Tuple[float, RematCandidate, float]] = []
    for c in cands:
        total = float(fixed_bytes) + L * c.saved_bytes + residual
        fits = total <= float(budget_bytes)
        score = L * c.overhead_s(peak_flops, hbm_bps, offload_bps)
        rows.append({
            "policy": c.name, "granularity": c.granularity,
            "saved_bytes_per_layer": c.saved_bytes,
            "recompute_flops": L * c.recompute_flops,
            "recompute_bytes": L * c.recompute_bytes,
            "offload_bytes": L * c.offload_bytes,
            "total_bytes": total, "fits": fits, "wired": c.wired,
            "overhead_s": score})
        if fits and c.wired:
            fitting.append((score, c, total))
    if fitting:
        best_score = min(s for s, _, _ in fitting)
        tied = [(c, tot) for s, c, tot in fitting if s == best_score]
        chosen, total = tied[0] if len(tied) == 1 else \
            tied[_tie_rng().randint(len(tied))]
        fits = True
        score = best_score
    else:
        chosen = next(c for c in cands if c.name == "save_nothing")
        total = float(fixed_bytes) + L * chosen.saved_bytes + residual
        fits = total <= float(budget_bytes)
        score = L * chosen.overhead_s(peak_flops, hbm_bps, offload_bps)
    return RematPlan(
        policy=chosen.name, granularity=chosen.granularity,
        use_recompute=chosen.granularity is not None,
        fits=fits, budget_bytes=float(budget_bytes),
        fixed_bytes=float(fixed_bytes),
        activation_bytes=L * chosen.saved_bytes,
        total_bytes=total,
        recompute_flops=L * chosen.recompute_flops,
        overhead_s=score, table=rows)
