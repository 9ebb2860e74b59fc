"""A whole run of the harness with the timed path broken underneath
must come out ``correct: false``; the same run unbroken comes out true.
The runs are rehearsals (tiny sizes, CPU): only the harness's look for a
chip is skipped, the rest of ``run.main`` is what a chip run executes."""

import json
import sys

import pytest

import run as harness


def _run(capsys, monkeypatch, workload, seed, seconds):
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", workload, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", "0", "--rehearse"])
    assert harness.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_sound_training_run_is_correct(capsys, monkeypatch):
    line, _ = _run(capsys, monkeypatch, "gpt2m-pretrain-1k", 2 ** 31 + 5, 1)
    assert line["correct"] is True and line["metrics"] == {}
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}


def test_a_step_that_returns_its_state_unchanged_is_caught(
        capsys, monkeypatch):
    from paddle2_tpu.optimizer import AdamW
    monkeypatch.setattr(AdamW, "_update_one",
                        lambda self, p, g, s, lr, step: (p, s))
    monkeypatch.setattr(AdamW, "_decoupled_wd", lambda self: False)
    monkeypatch.setattr(AdamW, "_weight_decay", ("l2", 0.0),
                        raising=False)
    line, out = _run(capsys, monkeypatch, "gpt2m-pretrain-1k", 17, 1)
    assert line["correct"] is False
    assert any("update_norm_gap" in ln and "OUTSIDE" in ln for ln in out)


def test_a_part_of_the_batch_left_out_is_caught(capsys, monkeypatch):
    """The step is fed the first half of every batch only."""
    from drivers import train
    real = train.Trainer.make_batch

    def half(self, index):
        ids, labels = real(self, index)
        keep = ids.shape[0] // 2
        return ids[:keep], labels[:keep]

    monkeypatch.setattr(train.Trainer, "make_batch", half)
    line, out = _run(capsys, monkeypatch, "gpt2m-pretrain-1k", 18, 1)
    assert line["correct"] is False
    assert any("OUTSIDE" in ln for ln in out)


def test_an_altered_served_token_is_caught(capsys, monkeypatch):
    from paddle2_tpu.serving.model_runner import PagedGPTRunner
    real = PagedGPTRunner.decode

    def altered(self, cache, ids, positions, tables):
        return (real(self, cache, ids, positions, tables) + 7) % 500

    monkeypatch.setattr(PagedGPTRunner, "decode", altered)
    line, out = _run(capsys, monkeypatch, "gpt2m-serve-longdoc-backlog", 19, 4)
    assert line["correct"] is False
    assert any("token_logit_gap" in ln and "OUTSIDE" in ln for ln in out)


def test_sound_serving_run_is_correct(capsys, monkeypatch):
    line, _ = _run(capsys, monkeypatch, "gpt2m-serve-longdoc-backlog", 20, 4)
    assert line["correct"] is True and line["failed"] == 0
