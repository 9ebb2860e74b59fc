"""An expert layer that holds a SHARE of the experts moves only the
assignments it holds (``DroplessExperts.route_and_run``: the sorted
prefix, ``HELD_CHUNK`` rows a trip), against the formulation every layer
had before — all ``T x k`` assignments sorted, gathered, gathered back
and summed — kept here as the reference. Float32 on the CPU, the grouped
matmul interpreted: both paths feed the experts the same rows, so the
outputs differ by the order of a token's at most ``k`` float32 terms."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.incubate import moe
from paddle2_tpu.incubate.moe import DroplessExperts
from paddle2_tpu.kernels.moe_gmm import gmm_plan, moe_gmm, plan_tile_rows

H, F, E, K = 32, 24, 8, 2
ROWS = 44       # trips of 4 rows: 22 for the 88 assignments
CHUNK = 4
N_COUNTS = len(DroplessExperts.COUNT_NAMES)


@pytest.fixture
def small_chunk(monkeypatch):
    """The loops at this file's sizes: four rows a trip (the layer takes
    them from ``HELD_CHUNK`` rows on, 512 as the cells run it)."""
    monkeypatch.setattr(moe, "HELD_CHUNK", CHUNK)


def parent_route_and_run(layer, a, valid=None, interpret=True):
    """The expert layer as it was before the held prefix (PR 45's
    ``route_and_run`` behind its router), with the count of rows moved
    that path reports: every assignment, there and back."""
    T, Hd = a.shape
    E, k = layer.num_experts, layer.k
    ids, w = layer.route(a)
    flat = ids.reshape(-1)
    held = (flat >= layer.first) & (flat < layer.first + layer.count)
    if valid is not None:
        held &= jnp.repeat(valid, k)
    rows_here = jnp.sum(jnp.any(held.reshape(T, k), -1))
    n_rows = T if valid is None else jnp.sum(valid)
    flat = jnp.where(held, flat, E)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=E + 1).astype(jnp.int32)
    n_held = jnp.sum(sizes[:E])
    rows = a[order // k]
    counts = jnp.stack([n_held, jnp.sum(sizes[:E] > 0),
                        jnp.max(sizes[:E]), rows_here,
                        n_rows]).astype(jnp.int32)
    plan = gmm_plan(sizes, T * k, layer.first, layer.count)
    counts = jnp.append(counts, plan_tile_rows(plan, T * k))
    gmm = functools.partial(moe_gmm, interpret=interpret, plan=plan)
    up = gmm(rows, layer.w1._data).astype(jnp.float32)
    if layer.gated:
        h = jax.nn.silu(up) * gmm(rows, layer.w3._data).astype(jnp.float32)
    else:
        h = jnp.square(jax.nn.relu(up))
    y = gmm(h.astype(a.dtype), layer.w2._data)
    inv = jnp.argsort(order)
    y = y[inv].reshape(T, k, Hd).astype(jnp.float32)
    out = jnp.sum(y * w[..., None], axis=1).astype(a.dtype)
    counts = jnp.append(counts, jnp.asarray(2 * T * k, jnp.int32))
    return out, jnp.concatenate([counts, ids.reshape(-1)])


def share(kind: str, held=(2, 2), lean: float = 0.0):
    """A layer holding ``held`` of the 8 experts whose selection bias
    leans ``lean`` towards (or away from) the experts it holds."""
    paddle.seed(7)
    kw = {} if kind == "gated" else dict(gated=False, activation="relu2")
    layer = DroplessExperts(H, F, E, K, held=held, std=0.2, **kw)
    bias = np.zeros(E, np.float32)
    bias[held[0]:held[0] + held[1]] = lean
    layer.expert_bias.set_value(paddle.to_tensor(bias))
    return layer


def activations(rows=ROWS, seed=3):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((rows, H)),
                       jnp.float32)


# the gather's loop; the sum's loop over chunks of ranked tokens and, in
# it, the loop over a chunk's terms
LOOPS = 3


def loops(layer, a) -> int:
    """Loops in the jaxpr of ``layer`` over ``a`` (one with a static
    trip count is written as a scan there; the visit list's search is
    one in every layer)."""
    text = str(jax.make_jaxpr(
        lambda x: layer.route_and_run(x, interpret=True))(a))
    return text.count("while[") + text.count("scan[") - 1


@pytest.mark.parametrize("kind", ["gated", "relu2"])
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("lean,held_of_all", [(-10.0, "none"),
                                              (0.0, "some"),
                                              (10.0, "all")])
def test_held_prefix_is_the_parents_layer(small_chunk, kind, padded, lean,
                                          held_of_all):
    """No assignment held, a handful, and EVERY one (both of a row's
    experts are the two held: the dropless worst case), with and without
    padding rows: the output within a float32 sum's reordering of the
    parent's, every count but the rows moved and every chosen id
    equal."""
    layer, a = share(kind, lean=lean), activations()
    valid = jnp.arange(ROWS) < 37 if padded else None
    got, record = layer.route_and_run(a, valid, interpret=True)
    want, ref_record = parent_route_and_run(layer, a, valid)
    n_rows = 37 if padded else ROWS
    n_held = int(record[0])
    assert {"none": n_held == 0, "some": 0 < n_held < n_rows * K,
            "all": n_held == n_rows * K}[held_of_all]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    if n_held:
        assert float(jnp.abs(want).max()) > 1e-3
    if padded:
        assert not np.asarray(got)[37:].any()
    moved = DroplessExperts.COUNT_NAMES.index("moe_rows_moved")
    assert moved == N_COUNTS - 1
    np.testing.assert_array_equal(np.delete(np.asarray(record), moved),
                                  np.delete(np.asarray(ref_record), moved))
    # the gather's trips over the prefix, the sum's over the tokens with
    # a first term and over those with a second, and the way back
    chosen = np.asarray(record[N_COUNTS:]).reshape(ROWS, K)[:n_rows]
    terms = ((chosen >= 2) & (chosen < 4)).sum(1)
    assert math.gcd(ROWS, moe.HELD_CHUNK) == CHUNK and terms.sum() == n_held
    trips = -(-n_held // CHUNK) + sum(-(-int((terms > r).sum()) // CHUNK)
                                      for r in range(K))
    assert int(record[moved]) == trips * CHUNK + ROWS
    assert int(ref_record[moved]) == 2 * ROWS * K


def test_held_prefix_with_the_first_experts_and_many_terms_a_row(small_chunk):
    """Experts [0, 4) of 8 at 4 a row: rows with up to four terms, the
    prefix starting at the first group."""
    paddle.seed(11)
    layer = DroplessExperts(H, F, E, 4, held=(0, 4), std=0.2)
    a = activations(40, 5)
    got, record = layer.route_and_run(a, interpret=True)
    want, ref_record = parent_route_and_run(layer, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(record)[:-1 - 160],
                                  np.asarray(ref_record)[:-1 - 160])
    assert loops(layer, a) == LOOPS


def test_parked_rows_are_never_read(small_chunk, monkeypatch):
    """What lies behind the prefix — of the gathered rows, which the
    chip leaves unwritten, and of the last product, whose unvisited rows
    nobody zeroes on this path — does not reach the output: poisoned
    with NaN, the layer's output does not change."""
    from paddle2_tpu.kernels import moe_gmm as kernel
    layer, a = share("gated"), activations()
    want, record = layer.route_and_run(a, interpret=True)
    n_held = int(record[0])
    assert 0 < n_held < ROWS * K
    poisoned = []

    def nan_empty(shape, dtype):
        poisoned.append(("rows", shape))
        return jnp.full(shape, jnp.nan, dtype)

    def nan_rest(lhs, rhs, plan, *, interpret, zero_rest=True):
        out = kernel_gmm(lhs, rhs, plan, interpret=interpret)
        if zero_rest:
            return out
        poisoned.append(("product", out.shape))
        return out.at[n_held:].set(jnp.nan)

    kernel_gmm = kernel._gmm
    monkeypatch.setattr(jax.lax, "empty", nan_empty)
    monkeypatch.setattr(kernel, "_gmm", nan_rest)
    got, _ = layer.route_and_run(a, interpret=True)
    # (the sums' buffer is such an allocation too: every chunk of it is
    # written)
    assert poisoned == [("rows", (ROWS * K, H)), ("product", (ROWS * K, H)),
                        ("rows", (ROWS, H))]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ["gated", "relu2"])
def test_a_layer_holding_every_expert_is_the_parents_program(small_chunk,
                                                             kind):
    """All experts held: no loop, and the lowered program is the
    parent's, text for text."""
    paddle.seed(7)
    kw = {} if kind == "gated" else dict(gated=False, activation="relu2")
    layer = DroplessExperts(H, F, E, K, std=0.2, **kw)
    a = activations()

    def run(x):
        return layer.route_and_run(x, interpret=True)

    def parent(x):
        return parent_route_and_run(layer, x)
    assert loops(layer, a) == 0
    # (the module's name is the function's)
    parent.__name__ = run.__name__
    assert jax.jit(run).lower(a).as_text() \
        == jax.jit(parent).lower(a).as_text()
    # and a share of them loops: the gather and the sum
    part = share(kind)
    assert loops(part, a) == LOOPS


@pytest.mark.parametrize("rows,k,looped", [
    (128, 8, False),     # K-EXAONE's decode step
    (128, 6, False),     # DeepSeek's
    (256, 6, False),     # Nemotron's
    (512, 8, True), (2048, 8, True), (8192, 8, True),
    (2048, 6, True), (3072, 6, True), (5120, 6, True), (512, 6, True)])
def test_which_static_shapes_take_the_loops(rows, k, looped):
    """The path follows the layer's own shapes: a share of the experts
    AND ``HELD_CHUNK`` rows or more — every prefill bucket of the three
    cells whose layers hold a share, none of their decode steps."""
    assert moe.HELD_CHUNK == 512
    layer = DroplessExperts(16, 8, 16, k, held=(0, 2))
    a = jax.ShapeDtypeStruct((rows, 16), jnp.float32)
    assert loops(layer, a) == (LOOPS if looped else 0)
    assert loops(DroplessExperts(16, 8, 16, k), a) == 0
