"""The comparison that decides ``correct``, at a size a test can hold:
its arithmetic, and that the served-token check REJECTS what it must."""

import numpy as np
import pytest

import checks
from reference import gpt2
from weights import make_weights

CFG = {"vocab_size": 300, "n_embd": 64, "n_layer": 2, "n_head": 4,
       "n_positions": 96, "layer_norm_epsilon": 1e-5,
       "initializer_range": 0.3}      # wide init: a model that attends


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": np.array([1.0, 2.0]), "b": np.array([1e-6]),
           "c": np.array([4.0])}
    prog = {"a": np.array([1.0, 2.2]), "b": np.array([3e-6]),
            "c": np.array([4.0])}
    gap, leaf = checks.worst_leaf_gap(prog, ref)
    # b is all but zero: its gap is taken against the median leaf (1.5),
    # so a[1] is the worst, 0.2 / 2.0
    assert leaf == "a[1]" and gap == pytest.approx(0.1)
    gap, leaf = checks.worst_leaf_gap({**prog, "c": np.array([np.nan])}, ref)
    assert gap == float("inf") and leaf == "c"


def test_unchanged_state_reads_a_full_gap():
    ref = {"w": np.array([0.5, 0.6])}
    gap, _ = checks.worst_leaf_gap({"w": np.zeros(2)}, ref)
    assert gap == pytest.approx(1.0)


def test_verdict_needs_every_number_inside_and_a_limit_for_each(capsys):
    assert checks.verdict({"x": 0.1, "y": 0.0}, {"x": 0.2, "y": 0})
    assert not checks.verdict({"x": 0.3, "y": 0.0}, {"x": 0.2, "y": 0})
    assert not checks.verdict({"x": float("nan")}, {"x": 0.2})
    assert "x: 0.3 limit 0.2 OUTSIDE" in capsys.readouterr().out
    with pytest.raises(KeyError):
        checks.verdict({"z": 0.0}, {"x": 1})


def test_sample_is_seeded_and_holds_the_longest():
    done = [{"prompt": [1] * n, "tokens": [2] * 4} for n in range(5, 25)]
    a = checks.sample_finished(done, 2 ** 31 + 7, 6)
    assert a == checks.sample_finished(done, 2 ** 31 + 7, 6)
    assert len(a) == 6 and a[0] is done[-1]
    assert a != checks.sample_finished(done, 8, 6)
    assert checks.sample_finished([], 1, 6) == []


def _greedy(params, prompt, n, zero_attention=False):
    """A stream decoded with the plain reference, one full forward per
    token over a row padded to the positions the model has (causal
    masking hides the padding), or, for the fault, with attention's
    output zeroed."""
    import jax
    import jax.numpy as jnp
    p = dict(params)
    if zero_attention:
        p["proj_w"] = jnp.zeros_like(p["proj_w"])
        p["proj_b"] = jnp.zeros_like(p["proj_b"])
    step = jax.jit(lambda ids, last: jnp.argmax(
        gpt2.logits(p, ids, CFG)[0, last]))
    toks = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(n):
            ids = np.zeros((1, CFG["n_positions"]), np.int32)
            ids[0, :len(toks)] = toks
            toks.append(int(step(jnp.asarray(ids), len(toks) - 1)))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def served():
    import jax.numpy as jnp
    seed = 11
    params = make_weights(gpt2.leaf_specs(CFG), seed, jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 300, n).tolist() for n in (9, 17, 30)]
    return seed, params, prompts


def _gap(seed, sample):
    ref = checks.reference_token_gaps(gpt2, CFG, seed, sample, 96, 16)
    return checks.serving_numbers(ref)["token_logit_gap"]


def test_served_token_check_accepts_the_reference_own_stream(served):
    seed, params, prompts = served
    sample = [{"prompt": p, "tokens": _greedy(params, p, 12)}
              for p in prompts]
    assert _gap(seed, sample) == 0.0


def test_served_token_check_rejects_a_shuffled_pairing(served):
    seed, params, prompts = served
    streams = [_greedy(params, p, 12) for p in prompts]
    shuffled = [{"prompt": p, "tokens": streams[(i + 1) % 3]}
                for i, p in enumerate(prompts)]
    assert _gap(seed, shuffled) > 1.0


def test_served_token_check_rejects_a_zeroed_attention_stream(served):
    seed, params, prompts = served
    broken = [{"prompt": p,
               "tokens": _greedy(params, p, 12, zero_attention=True)}
              for p in prompts]
    assert _gap(seed, broken) > 1.0


def test_control_reads_the_lower_precision_own_token(served):
    seed, params, prompts = served
    sample = [{"prompt": p, "tokens": _greedy(params, p, 12)}
              for p in prompts]
    low = checks.reference_token_gaps(gpt2, CFG, seed, sample, 96, 16,
                                      precision="int8")
    # every gap is of SOME token against the best: never negative
    assert all((g >= 0).all() for g in low["gaps"])
    assert low["tokens"] == 36
