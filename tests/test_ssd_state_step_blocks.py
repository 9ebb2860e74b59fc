"""The block one grid step of ``ssm_state_step`` holds (``kernels/
ssd.py``, PERF.md section 6, PR 42): as many whole groups of heads as a
byte budget holds, else a divisor of one group — and a row's result
does not depend on the block it rode in. The interpreted kernel against
the ``jnp`` formula and a token of the recurrence at the two cells'
head counts and groups (``P`` and ``N`` reduced for the interpreter),
under budgets that give a block of several groups, of one group and of
part of a group; and the plan itself at both cells' published shapes,
pinned where the chip's table set it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle2_tpu.kernels import ssd

# (name, heads, groups): the two cells' mixers, one head a group, and
# three groups of eight (sixteen heads divide no row of 24: a budget for
# two groups falls back to one)
HEADS = [("nemotron", 64, 8), ("falcon_h1", 32, 2), ("head_a_group", 8, 8),
         ("three_groups", 24, 3)]
P, N = 16, 128
HEAD_BYTES = P * N * 4


def budgets(nh, G):
    """Budgets (in heads) that make the plan hold several groups, one
    group and part of a group — where the head count has each."""
    per_group = nh // G
    want = {"groups": 2 * per_group, "group": per_group,
            "part": per_group // 2}
    return {k: v for k, v in want.items() if 1 <= v <= nh}


CASES = [(name, nh, G, kind, heads)
         for name, nh, G in HEADS
         for kind, heads in budgets(nh, G).items()]


def step_inputs(rng, R, nh, G, layers=2, slots=None):
    S = R + 3
    pool = jnp.asarray(rng.normal(size=(layers, S, nh, P, N)), jnp.float32)
    if slots is None:
        slots = rng.permutation(np.arange(1, S))[:R]
    args = dict(
        x=jnp.asarray(rng.normal(size=(R, nh, P)), jnp.float32),
        B=jnp.asarray(rng.normal(size=(R, G, N)) * 0.3, jnp.float32),
        C=jnp.asarray(rng.normal(size=(R, G, N)) * 0.3, jnp.float32),
        dt=jnp.asarray(rng.uniform(0.01, 0.3, size=(R, nh)), jnp.float32),
        A=-jnp.asarray(rng.uniform(0.5, 2.0, size=(nh,)), jnp.float32),
        D=jnp.asarray(rng.normal(size=(nh,)), jnp.float32))
    return pool, jnp.asarray(slots, jnp.int32), args


def order(a):
    return a["x"], a["B"], a["C"], a["dt"], a["A"], a["D"]


@pytest.fixture()
def budget(monkeypatch):
    """Set the byte budget to so many heads, for one test."""
    def set_heads(heads):
        monkeypatch.setattr(ssd, "STATE_BLOCK_BYTES", heads * HEAD_BYTES)
        ssd._state_step.clear_cache()
    yield set_heads
    ssd._state_step.clear_cache()


@pytest.mark.parametrize("name,nh,G,kind,heads", CASES,
                         ids=[f"{c[0]}-{c[3]}" for c in CASES])
def test_a_rows_result_does_not_depend_on_its_block(budget, name, nh, G,
                                                    kind, heads):
    budget(heads)
    hb, steps = ssd.state_step_plan(nh, G, P, N)
    per_group = nh // G
    assert hb * steps == nh and hb <= heads
    if kind == "groups":
        # whole groups; sixteen heads divide no row of 24: one group
        assert hb == (heads if nh % heads == 0 else per_group)
    elif kind == "group":
        assert hb == per_group
    else:
        assert hb == heads and per_group % hb == 0
    rng = np.random.default_rng(nh + heads)
    pool, slots, a = step_inputs(rng, 3, nh, G)
    got_pool, got_y = ssd.ssm_state_step(pool, 1, slots, *order(a),
                                         interpret=True)
    want_pool, want_y = ssd.ssm_state_step_xla(pool, 1, slots, *order(a))
    assert got_y.dtype == jnp.float32 and got_pool.dtype == jnp.float32
    assert float(jnp.abs(got_y - want_y).max()) <= 1e-5
    assert float(jnp.abs(got_pool - want_pool).max()) <= 1e-6
    # the other layer and the slots no row names: bit for bit as before
    assert bool((got_pool[0] == pool[0]).all())
    rest = np.setdiff1d(np.arange(pool.shape[1]), np.asarray(slots))
    assert bool((got_pool[1, rest] == pool[1, rest]).all())
    # a step of the kernel = a token of the recurrence
    y1, H1 = ssd.ssm_recurrence(a["x"][:1], a["dt"][:1], a["A"], a["B"][:1],
                                a["C"][:1], a["D"], h0=pool[1, slots[0]])
    assert float(jnp.abs(got_pool[1, slots[0]] - H1).max()) <= 1e-5
    assert float(jnp.abs(got_y[0] - y1[0]).max()) <= 1e-5


@pytest.mark.parametrize("name,nh,G", HEADS, ids=[h[0] for h in HEADS])
def test_every_block_gives_the_same_bits(budget, name, nh, G):
    """The block is a tiling, not arithmetic: whole row, one group or
    part of one, a head's state and output come out bit for bit."""
    rng = np.random.default_rng(nh)
    pool, slots, a = step_inputs(rng, 2, nh, G)
    seen = []
    for heads in sorted(set(budgets(nh, G).values()) | {nh}):
        budget(heads)
        seen.append(ssd.ssm_state_step(pool, 0, slots, *order(a),
                                       interpret=True))
    for got_pool, got_y in seen[1:]:
        assert bool((got_pool == seen[0][0]).all())
        assert bool((got_y == seen[0][1]).all())


@pytest.mark.parametrize("name,nh,G", HEADS[:2], ids=[h[0] for h in HEADS[:2]])
def test_padded_rows_share_the_garbage_slot(budget, name, nh, G):
    """Rows 1 and 3 are padding: both land in slot 0, in order, and the
    real rows' states and outputs are the formula's."""
    budget(nh // G)
    rng = np.random.default_rng(5)
    pool, slots, a = step_inputs(rng, 5, nh, G, slots=[4, 0, 2, 0, 7])
    got_pool, got_y = ssd.ssm_state_step(pool, 1, slots, *order(a),
                                         interpret=True)
    want_pool, want_y = ssd.ssm_state_step_xla(pool, 1, slots, *order(a))
    live = np.asarray([0, 2, 4])
    assert float(jnp.abs(got_y - want_y)[live].max()) <= 1e-5
    assert float(jnp.abs(got_pool - want_pool)[:, 1:].max()) <= 1e-6
    assert bool(jnp.isfinite(got_pool[1, 0]).all())


def test_two_layers_through_one_aliased_pool(budget):
    """Two layers' steps in one program, the pool donated: each layer's
    slots stepped once, the third layer untouched."""
    nh, G = 64, 8
    budget(2 * nh // G)
    rng = np.random.default_rng(9)
    pool, slots, a = step_inputs(rng, 3, nh, G, layers=3)
    kept = np.asarray(pool)

    @jax.jit
    def two_layers(pool, slots, *args):
        ys = []
        for layer in (0, 2):
            pool, y = ssd.ssm_state_step(pool, layer, slots, *args,
                                         interpret=True)
            ys.append(y)
        return pool, ys

    got_pool, ys = two_layers(pool, slots, *order(a))
    for layer, y in zip((0, 2), ys):
        want_pool, want_y = ssd.ssm_state_step_xla(
            jnp.asarray(kept), layer, slots, *order(a))
        assert float(jnp.abs(y - want_y).max()) <= 1e-5
        assert float(jnp.abs(got_pool[layer] - want_pool[layer]).max()) \
            <= 1e-6
    assert bool((np.asarray(got_pool[1]) == kept[1]).all())


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_dt_x_rides_the_mxu_exactly(jitted):
    """The three bfloat16 parts sum back to the float32 bit for bit —
    what lets the column broadcast be a product with ones — and each is
    a bfloat16 already before its cast, so a compiler that keeps excess
    precision across a cast (the chip's does) cannot change them."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(np.concatenate([
        rng.normal(size=4096) * 10.0 ** rng.integers(-6, 6, 4096),
        [0.0, 1.0, -1.0, 3.0e-30, 1.0e30, 1.0 + 2.0 ** -23]]), jnp.float32)
    split = jax.jit(ssd._split3) if jitted else ssd._split3
    hi, mid, lo = split(x)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    back = (hi.astype(jnp.float32) + mid.astype(jnp.float32)) \
        + lo.astype(jnp.float32)
    assert bool((back == x).all())
    # cut, not rounded: no part is larger than what it was cut from
    assert bool((jnp.abs(hi.astype(jnp.float32)) <= jnp.abs(x)).all())
    assert bool((mid.astype(jnp.float32) * x >= 0).all())


# the plan at the two cells' published shapes, where the chip's table
# set it (2 MB of float32 state a grid step): (nh, G, P, N) -> heads a
# grid step, grid steps a row, scoped VMEM a call asks for
CELL_PLANS = [
    ("nemotron3n", (64, 8, 64, 128), 64, 1,
     2 * (2 * (2 << 20) + 64 * 128 * 4 + 64 * 2 * 128 * 2
          + 2 * 8 * 128 * 4 + 64 * 64 * 4)),
    ("falconh1", (32, 2, 128, 256), 16, 2,
     2 * (2 * (2 << 20) + 16 * 256 * 4 + 128 * 1 * 128 * 2
          + 2 * 2 * 256 * 4 + 128 * 16 * 4)),
]


@pytest.mark.parametrize("cell,shape,hb,steps,vmem", CELL_PLANS,
                         ids=[c[0] for c in CELL_PLANS])
def test_the_plan_at_the_cells_shapes(cell, shape, hb, steps, vmem):
    assert ssd.STATE_BLOCK_BYTES == 2 << 20
    assert ssd.state_step_plan(*shape) == (hb, steps)
    assert ssd.state_step_vmem_bytes(*shape) == vmem
    # in and out, each double-buffered, and the small operands: well
    # inside the 16 MiB a kernel gets without asking
    assert vmem < (16 << 20) * 0.55
    nh, G, Pc, Nc = shape
    rows, layers = 256, 4
    assert ssd.state_step_counts(rows, (layers, nh, Pc, Nc), G) == dict(
        ssm_block_bytes=hb * Pc * Nc * 4,
        ssm_grid_steps=rows * layers * steps)


@pytest.mark.parametrize("shape,hb", [
    ((64, 8, 64, 128), 64),         # the whole row fits
    ((128, 8, 64, 128), 64),        # four of eight groups
    ((24, 3, 64, 128), 24),
    ((24, 3, 128, 256), 8),         # 16 heads fit, but divide no 24
    ((32, 2, 128, 256), 16),
    ((32, 2, 256, 512), 4),         # part of a group
    ((4, 1, 1024, 1024), 1),        # a head past the budget: one head
])
def test_the_plan_reads_shapes_only(shape, hb):
    nh, G, _, _ = shape
    got, steps = ssd.state_step_plan(*shape)
    assert (got, steps) == (hb, nh // hb)
    per_group = nh // G
    assert got % per_group == 0 or per_group % got == 0
