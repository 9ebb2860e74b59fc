"""The one traffic generator on the committed mixes."""

import collections

import numpy as np
import pytest

import trafficgen

CHAT = trafficgen.load_traffic("chat-steady")
BACKLOG = trafficgen.load_traffic("longdoc-backlog")
PRE = trafficgen.load_traffic("pretrain-8x1024")
FT = trafficgen.load_traffic("finetune-28x512")
BIG = 2 ** 31 + 12345        # the driver's seeds pass 32 signed bits


def test_same_seed_same_requests():
    a = trafficgen.requests(CHAT, BIG, 30.0, 50257)
    b = trafficgen.requests(CHAT, BIG, 30.0, 50257)
    assert a == b
    c = trafficgen.requests(CHAT, BIG + 1, 30.0, 50257)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


def test_lengths_inside_clips_and_on_the_ladder():
    reqs = trafficgen.requests(CHAT, 5, 45.0, 50257)
    ladder = set(CHAT["prompt_len"]["round_up_to"])
    assert {len(r["prompt"]) for r in reqs} <= ladder
    lo, hi = CHAT["output_len"]["min"], CHAT["output_len"]["max"]
    assert all(lo <= r["max_new"] <= hi for r in reqs)
    assert all(len(r["prompt"]) + r["max_new"] <= 1024 for r in reqs)
    assert all(1 <= t < 50257 for r in reqs for t in r["prompt"])


def test_every_seed_offers_the_same_work_in_another_order():
    """The sets of prompt lengths, output lengths and gaps are the
    file's laws at evenly spaced quantiles, the same for every seed;
    --seed orders them and draws the token ids."""
    runs = [trafficgen.requests(mix, s, 45.0, 50257)
            for mix in (CHAT, BACKLOG) for s in (1, 2, BIG)]
    for mix_runs in (runs[:3], runs[3:]):
        sets = [(collections.Counter(len(r["prompt"]) for r in reqs),
                 collections.Counter(r["max_new"] for r in reqs),
                 sorted(np.round(np.diff([0.0] + [r["due_s"] for r in reqs]),
                                 9)))
                for reqs in mix_runs]
        assert sets[0] == sets[1] == sets[2]
        assert [r["max_new"] for r in mix_runs[0]] != \
            [r["max_new"] for r in mix_runs[1]]
        assert mix_runs[0][0]["prompt"] != mix_runs[1][0]["prompt"]


def test_lengths_come_in_rounds():
    """Every 16 consecutive requests hold one length from each
    sixteenth of the set, so any stretch of a run is made up alike."""
    n = 540
    order = trafficgen._in_rounds(n, np.random.default_rng(BIG))
    assert sorted(order.tolist()) == list(range(n))
    for k in range(0, n // 16 * 16, 16):
        assert set((order[k:k + 16] * 16 // n).tolist()) == set(range(16))
    sums = [sum(len(r["prompt"]) for r in
                trafficgen.requests(BACKLOG, s, 45.0, 50257)[:150])
            for s in (1, 2, 3, BIG)]
    assert (max(sums) - min(sums)) / min(sums) < 0.02


def test_the_population_follows_the_law():
    """Evenly spaced quantiles of lognormal(median 128, sigma 0.8): the
    middle of the set is the median's rung of the ladder, and the
    exponential gaps of a Poisson process have mean 1 / rate."""
    pop = trafficgen.population(CHAT, 45.0)
    assert np.median(pop["prompt_len"]) == 128
    assert 44 <= np.median(pop["output_len"]) <= 52
    gaps = pop["gaps"]
    assert abs(gaps.mean() * CHAT["arrival"]["rate_per_s"] - 1) < 0.02
    assert 0.9 < gaps.std() / gaps.mean() < 1.05


def test_a_backlog_is_due_at_once_and_fits_the_context():
    reqs = trafficgen.requests(BACKLOG, BIG, 45.0, 50257)
    assert len(reqs) == 540 and {r["due_s"] for r in reqs} == {0.0}
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 1024
    assert {len(r["prompt"]) for r in reqs} <= set(
        BACKLOG["prompt_len"]["round_up_to"])


def test_arrivals_are_sorted_at_the_rate_and_stop_before_the_end():
    reqs = trafficgen.requests(CHAT, 9, 45.0, 50257)
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and due[0] >= 0.0
    horizon = 45.0 - CHAT["arrival"]["quiet_tail_s"]
    assert due[-1] < horizon
    assert len(reqs) == round(CHAT["arrival"]["rate_per_s"] * horizon)


@pytest.mark.parametrize("process,extra", [
    ("at-once", {}), ("gamma-burst", {"cv": 3.0}), ("poisson", {})])
def test_arrival_processes(process, extra):
    mix = dict(CHAT, arrival=dict(CHAT["arrival"], process=process, **extra))
    due = [r["due_s"] for r in trafficgen.requests(mix, 3, 20.0, 1000)]
    assert due == sorted(due) and due[-1] <= 20.0
    if process == "at-once":
        assert set(due) == {0.0}
    if process == "gamma-burst":
        gaps = np.diff(due)
        assert gaps.std() / gaps.mean() > 1.5      # burstier than Poisson


def test_shared_prefixes():
    mix = dict(CHAT, prefix_sharing={"groups": 2, "prefix_len": 16})
    reqs = trafficgen.requests(mix, 4, 20.0, 1000)
    heads = {tuple(r["prompt"][:16]) for r in reqs if len(r["prompt"]) > 16}
    assert len(heads) == 2


def test_training_batches_are_a_function_of_seed_and_index():
    ids, labels = trafficgen.batch(PRE, BIG, 3, 50257)
    ids2, labels2 = trafficgen.batch(PRE, BIG, 3, 50257)
    assert (ids == ids2).all() and (labels == labels2).all()
    assert ids.shape == labels.shape == (8, 1024) and ids.dtype == np.int32
    # next-token labels: the row shifted by one
    assert (ids[:, 1:] == labels[:, :-1]).all()
    assert ids.max() < 50257 and ids.min() >= 0
    # rows all differ, within a batch and from batch to batch
    other, _ = trafficgen.batch(PRE, BIG, 4, 50257)
    rows = {r.tobytes() for r in ids} | {r.tobytes() for r in other}
    assert len(rows) == 16
    # the law is skewed, so a loss can fall: half the mass in few ids
    assert np.median(ids) < 50257 / 20


def test_classification_labels_are_a_function_of_the_row():
    ids, labels = trafficgen.batch(FT, 7, 0, 40000)
    assert ids.shape == (28, 512) and labels.shape == (28,)
    assert (labels == ids[:, 0] % 2).all()
    assert trafficgen.tokens_per_batch(FT) == 28 * 512
