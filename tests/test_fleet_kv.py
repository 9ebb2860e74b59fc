"""ISSUE 16: fleet-global KV resilience.

The tiered HBM -> host-DRAM -> peer-DCN prefix store, prefix-affinity
failover routing, and KV migration instead of re-prefill. Everything
runs the REAL engine on CPU under virtual-clock stamps; the cross-tier
ledger (free + HBM-cache-held + host-tier + in-migration == usable,
refcount == claim multiplicity) must close after every mutation, and
every degraded path (corrupt spill, dropped migration) must fall back
to re-prefill — costing time, never tokens.
"""

import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.distributed.fault_tolerance import chaos
from paddle2_tpu.observability import tracing
from paddle2_tpu.serving import (BlockAllocator, EngineConfig,
                                 EngineFailoverRouter, FleetKVRegistry,
                                 HostKVTier, PrefixCache, ServingEngine,
                                 audit_kv_ledger, simulate_router,
                                 simulate_serving)
from paddle2_tpu.serving.simulate import cost_seconds

from served import shared_programs  # noqa: F401,E402

pytestmark = [pytest.mark.filterwarnings("ignore::DeprecationWarning"),
              pytest.mark.usefixtures("shared_programs")]


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.disarm()


@pytest.fixture(scope="module")
def tiny_model():
    from paddle2_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny(use_scan=False,
                                   max_position_embeddings=128))


def _engine(model, **over):
    kw = dict(block_size=16, num_blocks=24, max_batch=4,
              prefill_budget_tokens=128, max_model_len=128)
    kw.update(over)
    return ServingEngine(model, config=EngineConfig(**kw))


def _tiered(model, **over):
    kw = dict(enable_prefix_cache=True, enable_kv_spill=True,
              host_tier_blocks=64)
    kw.update(over)
    return _engine(model, **kw)


def _prompt(model, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, model.cfg.vocab_size, size=n).tolist()


def _ab_trace(model, n=8, seed=3, spacing=0.05):
    """Alternate two 32-token system prompts with distinct tails —
    serial arrivals so a tight prefix-cache cap cycles A/B through
    the spill tier between requests."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, model.cfg.vocab_size, size=32).tolist()
    b = rng.integers(0, model.cfg.vocab_size, size=32).tolist()
    out, t = [], 0.0
    for i in range(n):
        t += spacing
        tail = rng.integers(0, model.cfg.vocab_size, size=16).tolist()
        out.append({"arrival_t": t, "prompt": (a if i % 2 == 0 else b)
                    + tail, "max_new_tokens": 8})
    return out


def _drain(eng, max_steps=500):
    step = 0
    while not eng.idle() and step < max_steps:
        eng.tick(now=float(step))
        step += 1
    assert eng.idle(), "engine did not drain"


def _audit(eng):
    return audit_kv_ledger(
        eng.allocator,
        [s.table.blocks for s in eng.scheduler.running()],
        prefix_cache=eng.prefix_cache, host_tier=eng.host_tier)


# ------------------------------------------------------- host tier unit
def test_host_tier_crc_round_trip_and_eviction():
    tier = HostKVTier(capacity_blocks=2)
    k = np.arange(8, dtype=np.float32).reshape(2, 4)
    v = k * 2.0
    tier.put(("a",), k, v)
    got = tier.get(("a",))
    assert got is not None
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    # payloads are host-owned copies — mutating the source later
    # cannot scribble the tier
    k[0, 0] = 99.0
    np.testing.assert_array_equal(tier.get(("a",))[0].ravel()[0], 0.0)
    tier.put(("b",), k, v)
    tier.put(("c",), k, v)                 # capacity 2: LRU evicts "a"
    assert ("a",) not in tier and tier.evictions == 1
    tier.pop(("b",))                       # promotion retires the entry
    assert ("b",) not in tier and tier.fetched == 1
    # corrupt_one flips a byte but keeps the CRC: get() must detect
    key = tier.corrupt_one()
    assert key == ("c",)
    assert tier.get(("c",)) is None and tier.corrupt_drops == 1
    assert len(tier) == 0


# ------------------------------------------------- spill/fetch exactness
def test_spill_fetch_token_for_token(tiny_model):
    """ACCEPTANCE: HBM cache pressure degrades to host-tier fetches,
    not recompute — and the stream is token-for-token identical to
    the untired run while the cross-tier ledger stays closed."""
    trace = _ab_trace(tiny_model)
    e0 = _engine(tiny_model)
    simulate_serving(e0, [dict(r) for r in trace])
    toks0 = [e0.sequence(i).generated for i in range(len(trace))]

    e1 = _tiered(tiny_model, prefix_cache_blocks=3)
    simulate_serving(e1, [dict(r) for r in trace])
    toks1 = [e1.sequence(i).generated for i in range(len(trace))]
    assert toks1 == toks0
    assert e1.prefix_cache.spills > 0          # pressure spilled
    assert e1.prefix_cache.host_fetches > 0    # ...and hits fetched back
    assert len(e1.host_tier) > 0
    _audit(e1)


def test_spill_fetch_charges_clock_exactly(tiny_model, tmp_path):
    """The spill-fetch stall is charged on the virtual clock as its
    own component and the integer-picosecond decomposition still sums
    EXACTLY to end-to-end."""
    d = str(tmp_path / "t")
    tracing.enable(d, rank=0)
    trace = _ab_trace(tiny_model)
    e2 = _tiered(tiny_model, prefix_cache_blocks=3)
    step = 0
    for i, r in enumerate(trace):
        # serial: each request fully drains before the next arrives,
        # so the A/B alternation cycles prefixes through the spill
        # tier and every other lookup FETCHES
        e2.submit(r["prompt"], r["max_new_tokens"],
                  arrival_t=float(step), trace_id=i)
        while not e2.idle():
            e2.tick(now=float(step))
            step += 1
            assert step < 2000
    tracing.flush()
    tracing.disable()
    dec = tracing.decompose(tracing.load_trace_dir(d))
    fin = {t: c for t, c in dec.items() if c["finished"]}
    assert fin and all(c["exact"] for c in fin.values())
    assert sum(c["spill_fetches"] for c in fin.values()) > 0
    assert any(c["spill_fetch_s"] > 0 for c in fin.values())


# ------------------------------------------------- cross-tier ledger law
def test_cross_tier_ledger_property():
    """PROPERTY: across randomized spill / fetch / evict / insert /
    corrupt sequences the ledger closes exactly after EVERY op, and
    ``rebuild_free_list`` restores a clean allocator after a corrupt
    spill. No model needed — fake gather/scatter move deterministic
    bytes."""
    rng = np.random.default_rng(11)
    alloc = BlockAllocator(num_blocks=24, block_size=4)
    tier = HostKVTier(capacity_blocks=16)
    pc = PrefixCache(alloc, host_tier=tier)
    store = {}

    def gather(b):
        return store[b]

    def scatter(b, k, v):
        store[b] = (np.array(k), np.array(v))

    pc.set_spill_io(gather, scatter)
    live = []                     # block lists owned by fake sequences

    def payload(i):
        k = np.full((2, 2), float(i), np.float32)
        return k, k + 0.5

    for step in range(300):
        op = rng.integers(0, 5)
        if op == 0:               # insert a fresh 1-block prefix
            try:
                b = alloc.allocate(1)[0]
            except Exception:
                continue
            store[b] = payload(step)
            toks = [int(x) for x in rng.integers(0, 50, size=4)]
            mine = [b]
            live.append(mine)
            pc.insert(toks, mine)
        elif op == 1 and live:    # a sequence finishes
            mine = live.pop(rng.integers(0, len(live)))
            alloc.free(mine)
        elif op == 2:             # pressure: reclaim (spills)
            pc.reclaim(int(rng.integers(1, 4)))
        elif op == 3 and tier.keys():   # hit a spilled prefix
            key = tier.keys()[0]
            blocks, _ = pc.lookup(list(key))
            if blocks:
                live.append(blocks)
        elif op == 4 and tier.keys():   # host-DMA scribble
            key = tier.corrupt_one()
            assert tier.get(key) is None     # detected, dropped
        audit_kv_ledger(alloc, live, prefix_cache=pc, host_tier=tier)
    # chaos epilogue: rebuild from the survivors' claims and re-close
    alloc.rebuild_free_list(live + [pc.held_blocks()])
    audit_kv_ledger(alloc, live, prefix_cache=pc, host_tier=tier)


# ------------------------------------------------------- peer tier (DCN)
def test_peer_fetch_cost_gated_both_ways(tiny_model):
    """A cold engine fetches a LONG warm prefix from its peer over
    DCN (modeled transfer < modeled re-prefill) but re-prefills a
    SHORT one (DCN latency loses) — the same deterministic cost model
    decides both ways."""
    e0 = _tiered(tiny_model)
    e1 = _tiered(tiny_model)
    reg = FleetKVRegistry([e0, e1])
    P = _prompt(tiny_model, 96, seed=5)
    S = _prompt(tiny_model, 16, seed=6)
    # warm e0 with both prefixes; warm e1's SHORT prefill bucket so
    # its modeled re-prefill cost is real, not the fallback
    e0.submit(P, 2)
    e0.submit(S, 2)
    _drain(e0)
    e1.submit(_prompt(tiny_model, 16, seed=7), 2)
    _drain(e1)
    # long prefix: transfer wins -> peer fetch, token-for-token
    ref = _engine(tiny_model)
    ref.submit(P, 4)
    _drain(ref)
    rid = e1.submit(P, 4)
    _drain(e1)
    assert e1.prefix_cache.peer_fetches > 0
    assert reg.peer_fetch_blocks > 0
    assert e1.sequence(rid).generated == ref.sequence(0).generated
    # short prefix: the 250us DCN latency loses to a 16-token
    # re-prefill -> declined, recompute
    declined0 = reg.peer_declined
    e1.submit(S, 2)
    _drain(e1)
    assert reg.peer_declined > declined0
    _audit(e0), _audit(e1)


# --------------------------------------------- migration instead of re-prefill
def _migration_drill(model, arm=None, arm_early=False, prompt_len=96):
    """Warm engine 0 with a long prefix, spill it to host DRAM via
    cache pressure, queue a same-prefix request behind a long-running
    one, then KILL engine 0 — the adopter decides migrate vs
    re-prefill. ``arm_early`` arms the chaos spec BEFORE the warm
    phase (faults that must hit the spill tier while it fills).
    Returns (router, registry, rid, clean_tokens)."""
    P = _prompt(model, prompt_len, seed=5)
    filler = _prompt(model, 48, seed=8)
    short = _prompt(model, 16, seed=12)

    def fleet():
        engines = [_tiered(model, max_batch=1, prefix_cache_blocks=2)
                   for _ in range(2)]
        reg = FleetKVRegistry(engines)
        return EngineFailoverRouter(engines, probe_interval_s=1e-4,
                                    kv_registry=reg), reg

    # clean twin for token truth
    clean = _engine(model)
    clean.submit(P, 4)
    _drain(clean)
    clean_toks = clean.sequence(0).generated

    router, reg = fleet()
    if arm and arm_early:
        chaos.arm(arm)
    # the same-arrival `short` pair lands one copy on EACH engine, so
    # the adopter's 16-token prefill bucket has a REAL modeled cost
    # (not the fallback) when the migrate-vs-re-prefill decision runs
    warm = [{"arrival_t": 1e-4, "prompt": P, "max_new_tokens": 4},
            {"arrival_t": 0.1, "prompt": short, "max_new_tokens": 4},
            {"arrival_t": 0.1, "prompt": list(reversed(short)),
             "max_new_tokens": 4},
            {"arrival_t": 0.2, "prompt": filler, "max_new_tokens": 4},
            {"arrival_t": 0.21, "prompt": filler[:32],
             "max_new_tokens": 4},
            {"arrival_t": 0.22, "prompt": filler[:16],
             "max_new_tokens": 4}]
    simulate_router(router, warm)
    e0 = router.engines[0]
    keys = e0.prefix_cache._keys(P)
    assert all(k in e0.host_tier for k in keys), \
        "drill needs the whole prefix spilled to engine 0's host tier"
    if arm and not arm_early:
        chaos.arm(arm)
    # queue the same-prefix request (affinity -> engine 0), then kill
    # engine 0 BEFORE it is admitted: its KV exists ONLY in the dead
    # engine's host tier
    rid = router.submit(P, 4, arrival_t=1.0)
    assert router.home_of(rid) == 0
    e0.fail("drill", now=1.0)
    router.probe(now=1.0)
    return router, reg, rid, clean_toks


def _finish_rid(router, rid, t0=1.0):
    seq = router.sequence(rid)
    eng = router.engines[router.home_of(rid)]
    t = max(t0, getattr(seq, "kv_ready_t", 0.0)) + 1e-6
    for step in range(500):
        eng.tick(now=t + step * 1e-3)
        if seq.state.name == "FINISHED":
            return seq
    raise AssertionError("recovered sequence did not finish")


def test_migration_beats_reprefill_long_context(tiny_model):
    """ACCEPTANCE: on failover the adopter MIGRATES the dead engine's
    surviving host-tier blocks (modeled DCN transfer < modeled
    re-prefill), gates admission on the transfer landing, and the
    stream is token-for-token identical to the clean run."""
    router, reg, rid, clean_toks = _migration_drill(tiny_model)
    assert router.migrations == 1
    assert router.kv_migrated_blocks >= 5
    seq = router.sequence(rid)
    assert seq.kv_ready_t > 1.0            # admission gated on transfer
    # the modeled stall is the DCN transfer, cheaper than re-prefill
    eng = router.engines[router.home_of(rid)]
    stall = seq.kv_ready_t - 1.0
    full = cost_seconds(eng.runner.prefill_cost(
        eng.runner.prefill_padded_len(len(seq.tokens))))
    assert 0.0 < stall < full
    assert _finish_rid(router, rid).generated == clean_toks


def test_migration_declines_short_context(tiny_model):
    """Short context: the same cost model chooses re-prefill (DCN
    latency loses to a cheap prefill) — counted, and still exact."""
    router, reg, rid, clean_toks = _migration_drill(tiny_model,
                                                    prompt_len=16)
    assert router.migrations == 0
    assert router.migrations_declined >= 1
    assert router.sequence(rid).kv_ready_t == 0.0
    assert _finish_rid(router, rid).generated == clean_toks


def test_migration_chaos_drop_falls_back(tiny_model):
    """drop_migration: the transfer is lost on the virtual DCN — the
    adopter falls back to re-prefill from the token log, costing
    time, never tokens."""
    router, reg, rid, clean_toks = _migration_drill(
        tiny_model, arm="drop_migration:1")
    assert any(k == "drop_migration" for k, _ in chaos.fired_log())
    assert router.migrations == 0
    assert router.sequence(rid).kv_ready_t == 0.0
    assert _finish_rid(router, rid).generated == clean_toks


def test_migration_corrupt_spill_falls_back(tiny_model):
    """corrupt_spill_block scribbles the OLDEST spilled payload (the
    long prefix's first block): the CRC check drops it at migration
    time and the whole chain re-prefills — exact stream, closed
    ledger after rebuild."""
    router, reg, rid, clean_toks = _migration_drill(
        tiny_model, arm="corrupt_spill_block:1", arm_early=True)
    # the corruption fires inside engine 0's decode loop during the
    # warm phase (tier non-empty), before the kill
    assert any(k == "corrupt_spill_block" for k, _ in chaos.fired_log())
    seq = _finish_rid(router, rid)
    assert seq.generated == clean_toks
    eng = router.engines[router.home_of(rid)]
    eng.allocator.rebuild_free_list(
        [s.table.blocks for s in eng.scheduler.running()]
        + [eng.prefix_cache.held_blocks()])
    _audit(eng)


# --------------------------------------------------- prefix-affinity routing
def test_router_prefix_affinity(tiny_model):
    """Routing prefers the engine holding the longest cached prefix
    (HBM or host tier) over plain least-loaded; with no holder it
    falls back to least-loaded."""
    engines = [_tiered(tiny_model) for _ in range(2)]
    reg = FleetKVRegistry(engines)
    router = EngineFailoverRouter(engines, probe_interval_s=1e-4,
                                  kv_registry=reg)
    P = _prompt(tiny_model, 96, seed=5)
    r0 = router.submit(P, 2, arrival_t=0.0)
    assert router.home_of(r0) == 0
    _drain(engines[0])
    # engine 0 now holds P's prefix; even though engine 1 is
    # less-loaded after we queue filler on 0, P routes to 0
    router.submit(_prompt(tiny_model, 48, seed=9), 2, arrival_t=0.1)
    r1 = router.submit(P, 2, arrival_t=0.2)
    assert router.home_of(r1) == 0
    # no holder for a fresh prefix -> least-loaded (engine 1)
    r2 = router.submit(_prompt(tiny_model, 32, seed=10), 2,
                       arrival_t=0.3)
    assert router.home_of(r2) == 1
