"""The grouped matmul's tile plan (``kernels/moe_gmm.py``, PERF.md
section 6, PR 40): the row tile follows the rows a group gets, the
column tile is the widest the fast memory allows, and a row's result
does not depend on the tile it rode in — at the static shapes the
MoE cells reach (rows, groups, held share; K and N reduced for the
interpreter), at every row tile the rule can return. The fourth cell's
shapes (64 of 128 experts held, 6 a row) ride beside them."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle2_tpu.kernels import moe_gmm as G
from served import visits_by_hand

# (cell shape, rows routed T, k, groups routed over, first, held,
#  hidden K, width N): m = T x k rows, sizes count one parking group more
CELL_SHAPES = [
    ("sdar.decode", 256, 8, 128, 0, 128, 2048, 768),
    ("sdar.prefill512", 512, 8, 128, 0, 128, 2048, 768),
    ("sdar.prefill2048", 2048, 8, 128, 0, 128, 2048, 768),
    ("lfm2.decode", 64, 4, 64, 0, 64, 2048, 1536),
    ("lfm2.prefill1024", 1024, 4, 64, 0, 64, 2048, 1536),
    ("lfm2.prefill3072", 3072, 4, 64, 0, 64, 2048, 1536),
    ("dsv2.decode", 128, 6, 160, 0, 20, 5120, 1536),
    ("dsv2.prefill5120", 5120, 6, 160, 0, 20, 5120, 1536),
    ("nemotron.decode", 256, 6, 128, 0, 64, 2688, 1920),
    ("nemotron.prefill2048", 2048, 6, 128, 0, 64, 2688, 1920),
]
ROW_TILES = (64, 128, 256)


def routed_sizes(rng, T, k, E, first, held):
    """Sizes ``[E + 1]`` of T rows choosing k distinct experts of E with
    a popularity skew; experts outside the held share are parked in the
    last group, as ``DroplessExperts`` parks them."""
    score = rng.gumbel(size=(T, E)) + 0.4 * rng.standard_normal(E)
    ids = np.argsort(-score, 1)[:, :k].ravel()
    ids = np.where((ids >= first) & (ids < first + held), ids, E)
    return np.bincount(ids, minlength=E + 1).astype(np.int32)


@pytest.mark.parametrize("tm", ROW_TILES)
@pytest.mark.parametrize("shape", CELL_SHAPES, ids=lambda s: s[0])
def test_a_rows_result_does_not_depend_on_its_tile(monkeypatch, shape, tm):
    name, T, k, E, first, held, _, _ = shape
    m, K, N = T * k, 32, 256
    rng = np.random.default_rng(len(name) + T)
    sizes = routed_sizes(rng, T, k, E, first, held)
    lhs = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((held, K, N)), jnp.float32)
    monkeypatch.setattr(G, "_row_tile", lambda m, groups: tm)
    G._gmm.clear_cache()
    try:
        plan = G.gmm_plan(sizes, m, first, held)
        got = np.asarray(G.moe_gmm(lhs, rhs, interpret=True, plan=plan))
        assert int(plan[3]) == visits_by_hand(sizes, first, held, tm)
        assert int(G.plan_tile_rows(plan, m)) == int(plan[3]) * tm
    finally:
        G._gmm.clear_cache()
    want = np.asarray(G.gmm_reference(lhs, rhs, sizes, first))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # groups nobody holds (the parked rows): exactly zero
    ends = np.cumsum(sizes)
    lo, hi = ends[first] - sizes[first], ends[first + held - 1]
    assert not got[:lo].any() and not got[hi:].any()
    # a group that straddles row tiles is written once, by its own
    # visits: every held row differs from zero and equals the reference
    assert np.abs(got[lo:hi]).max(-1).min() > 0


# the tile the rule picks at each cell's static shapes: (rows, groups in
# the sizes, K, N) -> (row tile, column tile of the bf16 weights). A
# later edit to the rule shows here which cell it moves.
PICKED = [
    ("sdar.decode.up", 2048, 129, 2048, 768, 128, 768),
    ("sdar.decode.down", 2048, 129, 768, 2048, 128, 2048),
    ("sdar.prefill512.up", 4096, 129, 2048, 768, 128, 768),
    ("sdar.prefill1024.up", 8192, 129, 2048, 768, 128, 768),
    ("sdar.prefill2048.up", 16384, 129, 2048, 768, 128, 768),
    ("sdar.prefill2048.down", 16384, 129, 768, 2048, 128, 2048),
    ("lfm2.decode.up", 256, 65, 2048, 1536, 128, 512),
    ("lfm2.decode.down", 256, 65, 1536, 2048, 128, 512),
    ("lfm2.prefill1024.up", 4096, 65, 2048, 1536, 128, 768),
    ("lfm2.prefill2048.up", 8192, 65, 2048, 1536, 128, 768),
    ("lfm2.prefill3072.up", 12288, 65, 2048, 1536, 128, 768),
    ("lfm2.prefill3072.down", 12288, 65, 1536, 2048, 128, 1024),
    ("dsv2.decode.up", 768, 161, 5120, 1536, 128, 256),
    ("dsv2.decode.down", 768, 161, 1536, 5120, 128, 1024),
    ("dsv2.prefill2048.up", 12288, 161, 5120, 1536, 128, 256),
    ("dsv2.prefill3072.up", 18432, 161, 5120, 1536, 128, 256),
    ("dsv2.prefill5120.up", 30720, 161, 5120, 1536, 128, 256),
    ("dsv2.prefill5120.down", 30720, 161, 1536, 5120, 128, 1024),
    # a width of 1,856 lanes stored as 1,920 (15 x 128)
    ("nemotron.decode.up", 1536, 129, 2688, 1920, 128, 384),
    ("nemotron.decode.down", 1536, 129, 1920, 2688, 128, 384),
    ("nemotron.prefill512.up", 3072, 129, 2688, 1920, 128, 384),
    ("nemotron.prefill2048.up", 12288, 129, 2688, 1920, 128, 384),
    ("nemotron.prefill2048.down", 12288, 129, 1920, 2688, 128, 384),
    # a group's rows fill the tall tile: 8 experts, 512 rows each
    ("tall", 4096, 9, 2048, 1536, 256, 768),
    # rows that 128 does not divide, a width that 128 does not divide
    ("odd", 24, 7, 64, 48, 8, 48),
]


@pytest.mark.parametrize("case", PICKED, ids=lambda c: c[0])
def test_the_tile_the_rule_picks_at_each_cells_shape(case):
    _, m, groups, K, N, tm, tn = case
    assert G._row_tile(m, groups) == tm
    assert G._col_tile(N, K, 2, m) == tn
    assert m % tm == 0 and N % tn == 0
    # two weight tiles and two row tiles inside the 16 MB scoped VMEM
    assert K * tn * 2 <= 3 << 20


def test_plan_and_kernel_take_the_tile_from_the_same_place():
    """The plan's row tiles are in units of the kernel's: a plan made
    for 129 groups of 2,048 rows and the call that runs it agree (128
    rows), and a routing over fewer, fuller groups gets the tall tile in
    both."""
    for m, groups, tm in ((2048, 129, 128), (4096, 9, 256)):
        sizes = np.zeros(groups, np.int32)
        sizes[0] = m
        plan = G.gmm_plan(sizes, m, 0, groups - 1)
        assert int(plan[3]) == m // tm
        assert int(G.plan_tile_rows(plan, m)) == m
        assert plan[2][:m // tm].tolist() == list(range(m // tm))
