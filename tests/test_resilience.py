"""Training-loop resilience: self-healing resumable data pipeline,
rank-consistent numerical guardrails, deadline-aware collectives.

Chaos-driven end-to-end loops (ISSUE 2 acceptance):
* a worker crashed mid-epoch is respawned and the epoch yields every
  batch exactly once;
* poisoned gradients cause a skipped step with the scale backed off
  consistently, and training converges anyway;
* a stalled collective raises CollectiveTimeout naming the straggler
  rank within the deadline;
* with all guardrails enabled and no fault injected, per-step host
  syncs are unchanged (the sentinel is fused, not per-parameter).

Everything here is fast (well under 60 s total, no ``slow`` marks).
"""

import os
import signal
import time

import numpy as np
import pytest

import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.nn.functional as F
import paddle2_tpu.optimizer as opt
from paddle2_tpu.amp import GradScaler, ScaleSaturationError
from paddle2_tpu.distributed import collective
from paddle2_tpu.distributed.fault_tolerance import (
    AnomalyDetected, CheckpointManager, CollectiveTimeout, NonFiniteError,
    ReliableStep, StragglerDetector, TransientStepError, WorkerCrashError,
    chaos, numerics)
from paddle2_tpu.distributed.watchdog import CommWatchdog
from paddle2_tpu.io.dataloader import DataLoader, Dataset


@pytest.fixture(autouse=True)
def _clean_slate():
    chaos.disarm()
    StragglerDetector.get().reset()
    yield
    chaos.disarm()
    StragglerDetector.get().reset()
    CommWatchdog.get().consume_timeouts()
    paddle.set_flags({"FLAGS_check_loss_finite": False,
                      "FLAGS_debug_anomaly": False})


class _IdxDataset(Dataset):
    """Sample i is a [2] float32 vector of value i — batch contents are
    recoverable from the emitted tensors for exactness assertions."""

    def __init__(self, n, delay=0.0):
        self.n = n
        self.delay = delay

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if self.delay:
            time.sleep(self.delay)
        return np.full((2,), i, np.float32)


def _ids(batch):
    arr = batch[0] if isinstance(batch, (tuple, list)) else batch
    return [int(v) for v in np.asarray(arr.numpy())[:, 0]]


def _drain_ids(it):
    return [i for b in it for i in _ids(b)]


def _shm_available():
    try:
        from paddle2_tpu.io.native import load_shm_ring
        load_shm_ring()
        return True
    except RuntimeError:
        return False


# ------------------------------------------- DataLoader resumable state
class TestDataLoaderState:
    def test_mid_epoch_save_restore_exact_sequence(self):
        """Satellite acceptance: save mid-epoch, reload in a FRESH
        loader, and the exact remaining batch sequence (shuffle RNG
        included) continues — no duplicates, no gaps."""
        np.random.seed(1234)
        dl = DataLoader(_IdxDataset(23), batch_size=4, shuffle=True)
        it = iter(dl)
        consumed = []
        for _ in range(3):
            consumed += _ids(next(it))
        state = dl.state_dict()
        expected_rest = _drain_ids(it)      # what the original would do

        np.random.seed(999)                 # a fresh process's RNG differs
        dl2 = DataLoader(_IdxDataset(23), batch_size=4, shuffle=True)
        dl2.load_state_dict(state)
        rest = _drain_ids(iter(dl2))
        assert rest == expected_rest        # same order, same shuffle
        assert sorted(consumed + rest) == list(range(23))  # no dup/gap

    def test_subsequent_epoch_shuffle_also_replays(self):
        np.random.seed(7)
        dl = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True)
        it = iter(dl)
        next(it)
        state = dl.state_dict()
        _drain_ids(it)                      # finish epoch 0
        epoch1_original = _drain_ids(iter(dl))

        np.random.seed(4321)
        dl2 = DataLoader(_IdxDataset(12), batch_size=3, shuffle=True)
        dl2.load_state_dict(state)
        _drain_ids(iter(dl2))               # finish resumed epoch 0
        assert _drain_ids(iter(dl2)) == epoch1_original

    def test_state_between_epochs_is_fresh_start(self):
        dl = DataLoader(_IdxDataset(8), batch_size=2)
        _drain_ids(iter(dl))                # full epoch consumed
        state = dl.state_dict()
        assert state["batches"] is None and state["epoch"] == 1
        dl2 = DataLoader(_IdxDataset(8), batch_size=2)
        dl2.load_state_dict(state)
        assert _drain_ids(iter(dl2)) == list(range(8))

    def test_iterable_dataset_state_rejected(self):
        from paddle2_tpu.io.dataloader import IterableDataset

        class Stream(IterableDataset):
            def __iter__(self):
                return iter([np.float32(0)])

        dl = DataLoader(Stream(), batch_size=1)
        with pytest.raises(TypeError, match="IterableDataset"):
            dl.state_dict()

    def test_checkpoint_manager_round_trips_loader_state(self, tmp_path):
        """Tentpole wiring: the loader registers with CheckpointManager;
        a simulated preempt + restore in a fresh process resumes at the
        exact next batch."""
        np.random.seed(77)
        dl = DataLoader(_IdxDataset(20), batch_size=2, shuffle=True)
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        mgr.register_stateful("train_loader", dl)
        it = iter(dl)
        consumed = []
        for _ in range(4):
            consumed += _ids(next(it))
        mgr.save({"w": paddle.to_tensor([1.0])}, 4)
        expected_rest = _drain_ids(it)

        dl2 = DataLoader(_IdxDataset(20), batch_size=2, shuffle=True)
        mgr2 = CheckpointManager(str(tmp_path), keep_last=2)
        mgr2.register_stateful("train_loader", dl2)
        state = {"w": paddle.to_tensor([0.0])}
        assert mgr2.restore(state) == 4
        rest = _drain_ids(iter(dl2))
        assert rest == expected_rest
        assert sorted(consumed + rest) == list(range(20))


# --------------------------------------------- shm worker self-healing
@pytest.mark.skipif(not _shm_available(),
                    reason="no C++ toolchain for the native shm ring")
class TestWorkerSelfHealing:
    def test_chaos_worker_crash_respawns_exact_once(self):
        """Acceptance loop 1: a worker SIGKILLed mid-epoch is respawned
        and the epoch still yields every batch exactly once, in order."""
        chaos.arm("worker_crash:2:1")       # 2nd fetch kills worker 1
        dl = DataLoader(_IdxDataset(21, delay=0.01), batch_size=3,
                        num_workers=2)
        from paddle2_tpu.io.shm_loader import ShmProcessIter
        it = iter(dl)
        assert isinstance(it, ShmProcessIter)
        out = _drain_ids(it)
        assert [k for k, _ in chaos.fired_log()] == ["worker_crash"]
        assert out == list(range(21))       # ordered, exactly once

    def test_killed_before_first_batch_respawns(self):
        # 1.6 s of work a worker: one that a loaded host lets finish its
        # share before the kill lands needs no respawn (seen once, PR 44,
        # at 0.16 s a worker: `_restarts[0]` 0)
        dl = DataLoader(_IdxDataset(16, delay=0.2), batch_size=2,
                        num_workers=2)
        it = iter(dl)
        os.kill(it._procs[0], signal.SIGKILL)
        assert _drain_ids(it) == list(range(16))
        assert it._restarts[0] >= 1

    def test_budget_exhausted_escalates_transient(self):
        dl = DataLoader(_IdxDataset(12, delay=0.1), batch_size=2,
                        num_workers=2, worker_restarts=0)
        it = iter(dl)
        os.kill(it._procs[0], signal.SIGKILL)
        with pytest.raises(WorkerCrashError, match="restart budget"):
            _drain_ids(it)
        # the escalation is a TransientStepError: ReliableStep retries it
        assert issubclass(WorkerCrashError, TransientStepError)

    def test_dataset_exception_still_propagates_not_respawned(self):
        class Bad(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                if i == 3:
                    raise ValueError("decode exploded")
                return np.float32(i)

        dl = DataLoader(Bad(), batch_size=2, num_workers=2)
        with pytest.raises(ValueError, match="decode exploded"):
            list(iter(dl))

    def test_close_idempotent_and_bounded_with_hung_worker(self):
        """Satellite: a SIGSTOPped (hung) worker cannot block close() —
        bounded join, then SIGKILL; close() twice is a no-op."""
        from paddle2_tpu.io import shm_loader
        dl = DataLoader(_IdxDataset(40, delay=0.05), batch_size=2,
                        num_workers=2)
        it = iter(dl)
        victim = it._procs[0]
        os.kill(victim, signal.SIGSTOP)
        t0 = time.monotonic()
        it.close()
        assert time.monotonic() - t0 < shm_loader._JOIN_TIMEOUT_S + 3
        it.close()                          # idempotent
        # the stopped worker was SIGKILLed and reaped
        with pytest.raises(ProcessLookupError):
            os.kill(victim, 0)


# ------------------------------------------------ numerical guardrails
class TestNumericsSentinel:
    def test_nonfinite_flag_stays_on_device(self):
        import jax
        t = paddle.to_tensor(np.ones((4, 4), np.float32))
        flag = numerics.nonfinite_flag([t])
        assert isinstance(flag, jax.Array)  # no host sync happened
        assert numerics.flag_to_host(flag) is False
        bad = paddle.to_tensor(np.array([1.0, np.inf], np.float32))
        assert numerics.flag_to_host(numerics.nonfinite_flag(bad)) is True

    def test_int_only_tree_has_no_flag(self):
        t = paddle.to_tensor(np.arange(4, dtype=np.int64))
        assert numerics.nonfinite_flag([t]) is None
        assert numerics.flag_to_host(None) is False

    def test_all_reduce_found_inf_multicontroller(self, monkeypatch):
        """Rank consistency: a flag set on ANY process must come back
        True on EVERY process (max-reduce over the gossip)."""
        import jax
        from jax.experimental import multihost_utils as mhu
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(
            mhu, "process_allgather",
            lambda x: np.array([False, True]))  # peer rank found inf
        import jax.numpy as jnp
        local = jnp.asarray(False)              # WE did not
        assert numerics.all_reduce_found_inf(local) is True

    def test_assert_finite_raises_with_bisect_hint(self):
        numerics.assert_finite(1.25)            # clean: no raise
        with pytest.raises(NonFiniteError, match="debug_anomaly"):
            numerics.assert_finite(float("nan"))

    def test_debug_anomaly_names_first_bad_sublayer(self):
        class Poison(nn.Layer):
            def forward(self, x):
                return x * float("nan")

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(4, 4), Poison(), nn.Linear(4, 4))
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        with pytest.raises(AnomalyDetected) as ei:
            with numerics.debug_anomaly(model):
                model(x)
        assert ei.value.module_name == "1"      # the Poison layer


class TestGradScalerGuardrails:
    def _setup(self, **scaler_kw):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
        o = opt.SGD(learning_rate=0.05, parameters=model.parameters())
        scaler = GradScaler(init_loss_scaling=16.0, **scaler_kw)
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(8, 6).astype(np.float32))
        y = paddle.to_tensor(rs.randn(8, 3).astype(np.float32))
        return model, o, scaler, x, y

    def _one_step(self, model, o, scaler, x, y):
        loss = F.mse_loss(model(x), y)
        scaler.scale(loss).backward()
        scaler.step(o)
        scaler.update()
        o.clear_grad()
        return loss

    def test_poison_grads_skips_step_and_backs_off(self):
        """Acceptance loop 2: poisoned gradients -> skipped step (params
        untouched), scale halved, and training converges anyway."""
        model, o, scaler, x, y = self._setup()
        first = float(np.asarray(F.mse_loss(model(x), y)._data))
        self._one_step(model, o, scaler, x, y)
        before = [p.numpy().copy() for p in model.parameters()]
        chaos.arm("poison_grads:1")
        self._one_step(model, o, scaler, x, y)      # poisoned: skipped
        chaos.disarm()
        assert [k for k, _ in chaos.fired_log()] == []
        for p, b in zip(model.parameters(), before):
            np.testing.assert_array_equal(p.numpy(), b)  # step skipped
        assert scaler.get_loss_scaling() == pytest.approx(8.0)  # 16 * 0.5
        for _ in range(6):                           # converges anyway
            self._one_step(model, o, scaler, x, y)
        last = float(np.asarray(F.mse_loss(model(x), y)._data))
        assert np.isfinite(last) and last < first

    def test_scale_clamped_to_floor_and_ceiling(self):
        import jax.numpy as jnp
        model, o, scaler, x, y = self._setup(
            min_loss_scaling=8.0, max_loss_scaling=32.0,
            incr_every_n_steps=1)
        # bad steps can never push the scale below the floor
        for _ in range(4):
            loss = F.mse_loss(model(x), y)
            scaler.scale(loss).backward()
            for p in o._parameter_list():
                p.grad._replace_data(jnp.full(p.grad._data.shape, jnp.nan,
                                              p.grad._data.dtype))
            scaler.step(o)
            scaler.update()
            o.clear_grad()
        assert scaler.get_loss_scaling() == pytest.approx(8.0)
        # good steps can never push it above the ceiling
        for _ in range(4):
            self._one_step(model, o, scaler, x, y)
        assert scaler.get_loss_scaling() == pytest.approx(32.0)

    def test_saturation_error_after_consecutive_skips(self):
        import jax.numpy as jnp
        model, o, scaler, x, y = self._setup(max_consecutive_skips=3)
        with pytest.raises(ScaleSaturationError, match="3 consecutive"):
            for _ in range(5):
                loss = F.mse_loss(model(x), y)
                scaler.scale(loss).backward()
                for p in o._parameter_list():
                    p.grad._replace_data(
                        jnp.full(p.grad._data.shape, jnp.nan,
                                 p.grad._data.dtype))
                scaler.step(o)
                scaler.update()
                o.clear_grad()

    def test_clean_path_one_host_sync_regardless_of_param_count(self):
        """Acceptance: the sentinel is ONE fused readback per unscale,
        not one per parameter — host syncs don't scale with model size."""
        def syncs_for(n_layers):
            paddle.seed(0)
            layers = []
            for _ in range(n_layers):
                layers += [nn.Linear(6, 6), nn.ReLU()]
            model = nn.Sequential(*layers, nn.Linear(6, 3))
            o = opt.SGD(learning_rate=0.01,
                        parameters=model.parameters())
            scaler = GradScaler(init_loss_scaling=8.0)
            rs = np.random.RandomState(0)
            x = paddle.to_tensor(rs.randn(4, 6).astype(np.float32))
            y = paddle.to_tensor(rs.randn(4, 3).astype(np.float32))
            scaler.scale(F.mse_loss(model(x), y)).backward()
            before = numerics.host_sync_count()
            scaler.step(o)
            scaler.update()
            return numerics.host_sync_count() - before

        assert syncs_for(1) == syncs_for(4) == 1

    def test_fit_consumes_sentinel_under_flag(self):
        paddle.set_flags({"FLAGS_check_loss_finite": True})

        def nan_loss(pred, label):
            return (pred * float("nan")).mean()

        m = paddle.Model(nn.Sequential(nn.Linear(6, 3)))
        m.prepare(opt.SGD(learning_rate=0.01, parameters=m.parameters()),
                  nan_loss)
        with pytest.raises(NonFiniteError, match="debug_anomaly"):
            m.fit(_IdxDatasetPair(8), batch_size=4, epochs=1, verbose=0)


class _IdxDatasetPair(Dataset):
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        return (rs.randn(6).astype(np.float32),
                rs.randn(3).astype(np.float32))


# ------------------------------------------- deadline-aware collectives
class TestDeadlineCollectives:
    def test_barrier_timeout_names_straggler_within_deadline(self):
        """Acceptance loop 3: a stalled collective raises
        CollectiveTimeout naming the straggler rank, within (about) the
        deadline instead of hanging forever."""
        det = StragglerDetector.get()
        det.observe(0, 0.01)
        det.observe(1, 0.01)
        det.observe(2, 0.5)                  # 50x the median: straggling
        chaos.arm("stall_collective:1:2.0")
        t0 = time.monotonic()
        with pytest.raises(CollectiveTimeout) as ei:
            collective.barrier(timeout=0.3)
        assert time.monotonic() - t0 < 1.5   # raised near the deadline
        assert ei.value.stragglers == [2]
        assert "straggler" in str(ei.value)
        assert [k for k, _ in chaos.fired_log()] == ["stall_collective"]

    def test_all_reduce_timeout_clean_path_unaffected(self):
        from paddle2_tpu.distributed import mesh as mesh_mod
        w = mesh_mod.world_size()            # rank-major leading dim
        t = paddle.to_tensor(np.ones((w,), np.float32))
        collective.all_reduce(t, timeout=5.0)  # completes well inside
        assert float(np.asarray(t._data)[0]) == pytest.approx(float(w))

    def test_reliable_step_retries_collective_timeout(self):
        """The detect->recover wiring: a CollectiveTimeout inside the
        step is a retryable fault — ReliableStep restores and replays."""
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(6, 3))
        o = opt.SGD(learning_rate=0.05, parameters=model.parameters())
        rs = ReliableStep(model, o, snapshot_every=1, sleep=lambda _: None)
        chaos.arm("stall_collective:1:2.0")
        rsd = np.random.RandomState(0)
        x = paddle.to_tensor(rsd.randn(4, 6).astype(np.float32))
        y = paddle.to_tensor(rsd.randn(4, 3).astype(np.float32))

        def step(x, y):
            loss = F.mse_loss(model(x), y)
            loss.backward()
            collective.barrier(timeout=0.2)  # 1st call: stalled -> raise
            o.step()
            o.clear_grad()
            return loss

        out = rs.run(step, x, y)
        rs.finalize()
        assert rs.stats["retries"] >= 1
        assert np.isfinite(float(np.asarray(out._data)))

    def test_straggler_gossip_via_shared_dir(self, tmp_path, monkeypatch):
        from paddle2_tpu.distributed import watchdog
        monkeypatch.setenv(watchdog.GOSSIP_DIR_ENV, str(tmp_path))
        det = StragglerDetector.get()
        det.observe(0, 0.1)                  # writes rank.0 file
        peer = watchdog.StragglerDetector()  # a "different process"
        peer.observe(1, 0.1)
        peer.observe(2, 0.9)
        assert det.suspects() == [2]         # read through the dir
        assert sorted(os.listdir(str(tmp_path))) == [
            "rank.0", "rank.1", "rank.2"]

    def test_suspects_need_two_ranks(self):
        det = StragglerDetector.get()
        det.observe(0, 9.0)
        assert det.suspects() == []


# ------------------------------------------------ batch_isend_irecv
class TestBatchP2PValidation:
    def _t(self, shape=(1, 4), dtype=np.float32):
        return paddle.to_tensor(np.zeros(shape, dtype))

    @pytest.fixture(autouse=True)
    def _fresh_queue(self):
        collective._world_group()._p2p_queue.clear()
        yield
        collective._world_group()._p2p_queue.clear()

    def test_recv_without_send_rejected(self):
        ops = [collective.P2POp(collective.irecv, self._t(), 0)]
        with pytest.raises(ValueError, match="no.*matching earlier send"):
            collective.batch_isend_irecv(ops)

    def test_shape_mismatch_rejected_before_dispatch(self):
        ops = [collective.P2POp(collective.isend, self._t((1, 4)), 0),
               collective.P2POp(collective.irecv, self._t((1, 8)), 0)]
        with pytest.raises(ValueError, match="shapes must match"):
            collective.batch_isend_irecv(ops)
        assert not collective._world_group()._p2p_queue  # nothing queued

    def test_dtype_mismatch_rejected(self):
        ops = [collective.P2POp(collective.isend, self._t(), 0),
               collective.P2POp(collective.irecv,
                                self._t(dtype=np.int64), 0)]
        with pytest.raises(ValueError, match="dtypes must match"):
            collective.batch_isend_irecv(ops)

    def test_dangling_send_rejected(self):
        ops = [collective.P2POp(collective.isend, self._t(), 0)]
        with pytest.raises(ValueError, match="no matching recv"):
            collective.batch_isend_irecv(ops)

    def test_non_p2p_op_rejected(self):
        ops = [collective.P2POp(collective.all_reduce, self._t(), 0)]
        with pytest.raises(ValueError, match="isend/irecv"):
            collective.batch_isend_irecv(ops)


# --------------------------------------------------- chaos new kinds
def test_new_chaos_kinds_registered():
    for kind in ("worker_crash", "poison_grads", "stall_collective"):
        assert kind in chaos.KINDS
    inj = chaos.arm("worker_crash:2:1,poison_grads:1,stall_collective:1:9")
    assert inj.targets["worker_crash"] == (2, 1.0)
    assert inj.targets["stall_collective"] == (1, 9.0)


def test_disarmed_hooks_are_noops():
    assert chaos.active() is None
    chaos.maybe_stall_collective("x")
    chaos.maybe_crash_worker([os.getpid()])  # must NOT kill us
    class _O:
        def _parameter_list(self):
            raise AssertionError("must not be touched when disarmed")
    chaos.maybe_poison_grads(_O())
