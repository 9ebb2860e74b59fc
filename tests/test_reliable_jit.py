"""Reliability plane fused into the compiled train step.

Covers ``jit.train_step(..., reliability=...)`` — the instrumented
builder that computes the non-finite sentinel and the SDC gradient
fingerprint INSIDE the donated executable (one packed uint32[4] aux,
zero extra clean-path readbacks), schedules donation-safe snapshots,
and inherits ReliableStep's rewind+replay / flight-recorder /
quarantine wiring:

* clean-path transparency: instrumented losses and params bitwise equal
  the plain program, with zero added host syncs;
* eager-vs-compiled recovery parity on the same injected fault
  sequence (NaN batch, flipped mantissa bit);
* chaos parity: the traced ``flip_bits`` twin flips bitwise-identical
  positions to the eager mutation, and ``poison_grads`` fires inside
  the jitted step;
* AMP: GradScaler fused into the program — in-program skip, one packed
  readback total, scale backoff matching the eager cycle;
* donation safety: snapshots survive two restores around a donating
  step, set_state_dict never aliases a snapshot into a donation
  candidate, and the SnapshotAliasError fence trips on live leaves;
* compile-cache/MTTR accounting: ``compile`` flight events,
  ``elastic.compile_cache`` stream records, budget-blown warnings, and
  launcher env plumbing (--compile_cache_dir, PADDLE_MTTR_BUDGET);
* a ``-m gang`` 2-rank kill+respawn drill through the compiled step
  adopting a buddy replica.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.optimizer as opt
from paddle2_tpu.amp import GradScaler
from paddle2_tpu.distributed.fault_tolerance import (
    ReliabilityConfig, ReliableStep, ReliableTrainStep, SDCGuard,
    TransientStepError, chaos, flight_recorder, health, numerics)
from paddle2_tpu.distributed.fault_tolerance.reliable import (
    SnapshotAliasError, _assert_host_snapshot)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_chaos():
    chaos.disarm()
    yield
    chaos.disarm()


def _mlp(seed=0):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))


def _build(reliability=None, seed=0, optimizer=opt.AdamW, **opt_kw):
    m = _mlp(seed)
    opt_kw.setdefault("learning_rate", 1e-2)
    o = optimizer(parameters=m.parameters(), **opt_kw)
    step = paddle.jit.train_step(
        lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
        reliability=reliability)
    return m, o, step


def _batches(n, seed=0):
    rs = np.random.RandomState(seed)
    return [(paddle.to_tensor(rs.randn(16, 8).astype(np.float32)),
             paddle.to_tensor(rs.randn(16, 4).astype(np.float32)))
            for _ in range(n)]


def _weight(m):
    return np.asarray(m.state_dict()["0.weight"]._data).copy()


class TestInstrumentedProgram:
    def test_clean_path_bitwise_transparent_and_sync_free(self):
        batches = _batches(5)
        m1, _, plain = _build()
        ref = [float(plain(x, y)) for x, y in batches]

        m2, _, inst = _build(reliability=True)
        assert isinstance(inst, ReliableTrainStep)
        s0 = numerics.host_sync_count()
        got = [float(inst(x, y)) for x, y in batches]
        inst.finalize()
        # instrumentation must change NOTHING on the clean path: same
        # losses, same params, and the packed aux is never read
        assert numerics.host_sync_count() - s0 == 0
        assert got == ref
        assert np.array_equal(_weight(m1), _weight(m2))
        assert inst.stats["retries"] == 0

    def test_aux_is_packed_uint32_4(self):
        from paddle2_tpu.jit.train_step import TrainStepProgram
        m = _mlp()
        o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())
        prog = TrainStepProgram(
            lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
            instrument=True)
        x, y = _batches(1)[0]
        prog(x, y)
        aux = prog.last_aux
        assert aux is not None
        arr = np.asarray(aux)
        assert arr.shape == (4,) and arr.dtype == np.uint32
        assert int(arr[0]) == 0                  # clean grads
        found, host_fp = numerics.packed_sentinel_to_host(aux)
        assert found is False
        assert isinstance(host_fp[2], float) and host_fp[2] > 0.0

    def test_poison_fault_sets_nonfinite_lane_and_folds_loss(self):
        from paddle2_tpu.jit.train_step import TrainStepProgram
        m = _mlp()
        o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())
        prog = TrainStepProgram(
            lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
            instrument=True)
        prog.grad_fault_hook = lambda: ("poison",)
        x, y = _batches(1)[0]
        loss = prog(x, y)
        # grads were NaNed in-program: the sentinel lane trips AND the
        # loss is folded to NaN so a deferred loss check needs no extra
        # readback to notice
        assert np.asarray(prog.last_aux)[0] > 0
        assert not np.isfinite(float(loss))

    def test_flip_fault_changes_digest_not_nonfinite(self):
        from paddle2_tpu.distributed.fault_tolerance.sdc import \
            digest_fingerprint
        from paddle2_tpu.jit.train_step import TrainStepProgram

        def run(fault):
            m = _mlp()
            o = opt.AdamW(learning_rate=1e-2,
                          parameters=m.parameters())
            prog = TrainStepProgram(
                lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
                instrument=True)
            if fault:
                prog.grad_fault_hook = lambda: fault
            x, y = _batches(1)[0]
            loss = prog(x, y)
            found, host_fp = numerics.packed_sentinel_to_host(
                prog.last_aux)
            return float(loss), found, digest_fingerprint(host_fp)

        clean_loss, clean_found, clean_digest = run(None)
        flip_loss, flip_found, flip_digest = run(("flip", 1, 0))
        # the SDC simulation: values shift, nothing goes non-finite,
        # the loss stays clean — only the fingerprint digest moves
        assert flip_found is False and clean_found is False
        assert np.isfinite(flip_loss)
        assert flip_digest != clean_digest

    def test_reliability_arg_validation(self):
        with pytest.raises(TypeError):
            _build(reliability="yes")
        m, o, step = _build(reliability={"snapshot_every": 3})
        assert step.snapshot_every == 3
        cfg = ReliabilityConfig(max_retries=7)
        _, _, step2 = _build(reliability=cfg)
        assert step2.max_retries == 7

    def test_scaler_with_accumulation_rejected(self):
        import paddle2_tpu.distributed as dist
        paddle.seed(0)
        m = nn.Linear(4, 2)
        o = dist.shard_optimizer(
            opt.SGD(learning_rate=0.1, parameters=m.parameters()),
            gradient_accumulation_steps=2)
        step = paddle.jit.train_step(
            lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
            reliability=ReliabilityConfig(scaler=GradScaler()))
        with pytest.raises(NotImplementedError):
            step(paddle.ones([2, 4]), paddle.zeros([2, 2]))


class TestChaosParity:
    def test_traced_flip_bitwise_matches_eager_flip(self):
        """The compiled drill must corrupt the SAME bits the eager one
        does: _flip_bits_traced vs flip_mantissa_bits on equal input."""
        import jax.numpy as jnp
        from paddle2_tpu.distributed.fault_tolerance.chaos import \
            _flip_bits_traced
        for dtype in (np.float32, "bfloat16"):
            a = np.random.RandomState(3).randn(4, 6).astype(np.float32)
            arr = jnp.asarray(a).astype(dtype) \
                if dtype == "bfloat16" else jnp.asarray(a)
            for seed in (0, 1, 7):
                eager = chaos.flip_mantissa_bits(arr, 3, seed=seed)
                traced = _flip_bits_traced(arr, 3, seed)
                assert np.array_equal(
                    np.asarray(eager).view(np.uint8),
                    np.asarray(traced).view(np.uint8)), (dtype, seed)

    def test_env_gated_chaos_reaches_compiled_step(self, monkeypatch):
        """FLAGS_chaos flip_bits:grads fires inside the jitted step on
        the victim rank only — same gating as the eager hook."""
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        chaos.arm("flip_bits:grads:2:0")      # victim rank 0: not us
        batches = _batches(3)
        m1, _, s1 = _build(reliability=True)
        for x, y in batches:
            s1(x, y)
        s1.finalize()
        assert chaos.active().counts["flip_bits"] == 0
        chaos.disarm()

        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
        chaos.arm("flip_bits:grads:2:0")      # victim: fires once
        m2, _, s2 = _build(reliability=True)
        for x, y in batches:
            s2(x, y)
        s2.finalize()
        assert ("flip_bits", "grads:rank0:2bits:compiled") \
            in chaos.fired_log()
        # a flip alone (no SDC vote in world 1) corrupts silently —
        # exactly the SDC threat model: finite losses, diverged weights
        assert not np.array_equal(_weight(m1), _weight(m2))

    def test_poison_grads_is_amp_only_like_eager(self):
        """Parity regression (review finding): the eager poison_grads
        fault only has a call site inside GradScaler.unscale_ — a
        non-AMP compiled run must be the same no-op, or an A/B drill
        reports a spurious eager-vs-compiled difference."""
        chaos.arm("poison_grads:1")
        m, _, step = _build(reliability=True)      # no scaler
        for x, y in _batches(2):
            step(x, y)
        step.finalize()
        assert chaos.active().counts["poison_grads"] == 0
        assert step.stats["retries"] == 0


class TestRecoveryParity:
    def test_nan_batch_recovery_eager_vs_compiled(self):
        """Same injected fault sequence (poison_loss at the 3rd step)
        through BOTH paths: each recovers to a state bitwise identical
        to its own clean run, with identical retry accounting."""
        batches = _batches(6)

        def eager(arm):
            m = _mlp()
            o = opt.AdamW(learning_rate=1e-2,
                          parameters=m.parameters())
            rel = ReliableStep(m, o, snapshot_every=1)
            if arm:
                chaos.arm("poison_loss:3")

            def step(x, y):
                loss = ((m(x) - y) ** 2).mean()
                loss.backward()
                o.step()
                o.clear_grad()
                return loss
            for x, y in batches:
                rel.run(step, x, y)
            rel.finalize()
            chaos.disarm()
            return _weight(m), rel.stats

        def compiled(arm):
            m, o, step = _build(reliability=True)
            if arm:
                chaos.arm("poison_loss:3")
            for x, y in batches:
                step(x, y)
            step.finalize()
            chaos.disarm()
            return _weight(m), step.stats

        e_clean, _ = eager(False)
        e_fault, e_stats = eager(True)
        c_clean, _ = compiled(False)
        c_fault, c_stats = compiled(True)
        assert e_stats["retries"] == 1 and c_stats["retries"] == 1
        assert e_stats["restores"] == 1 and c_stats["restores"] == 1
        # bitwise-faithful recovery on each path...
        assert np.array_equal(e_fault, e_clean)
        assert np.array_equal(c_fault, c_clean)
        # ...and the two paths land on the same trained model (bitwise
        # across the fused-vs-three-phase boundary holds on this CPU
        # lowering; the contract across backends is allclose)
        np.testing.assert_allclose(c_fault, e_fault, rtol=1e-5,
                                   atol=1e-6)

    def test_flip_detect_retry_2replicas_compiled(self, tmp_path):
        """The SDC drill through the COMPILED step: two replica
        threads, replica 1's program flips a mantissa bit at step 2;
        the in-program fingerprints disagree, every rank rewinds via
        GradientCorruptionError, the replay is clean, and the replicas
        end bitwise identical — eager ReliableStep's drill semantics,
        inherited by the builder."""
        batches = _batches(4)
        built = []
        for r in range(2):
            m = _mlp()
            o = opt.AdamW(learning_rate=1e-2,
                          parameters=m.parameters())
            built.append((m, o))
        results = {}

        def run_replica(r):
            m, o = built[r]
            g = SDCGuard(optimizer=None, store_dir=str(tmp_path / "ex"),
                         rank=r, world=2, timeout=20.0,
                         poll_interval=0.005, evict=False,
                         quarantine=health.QuarantineStore(
                             str(tmp_path / "q")))
            step = paddle.jit.train_step(
                lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
                reliability=ReliabilityConfig(sdc=g))
            fired = {"done": False}

            def hook():
                if r == 1 and step._step == 2 and not fired["done"]:
                    fired["done"] = True
                    return ("flip", 2, 0)
                return None
            step.program.grad_fault_hook = hook
            for x, y in batches:
                step(x, y)
            step.finalize()
            results[r] = {"retries": step.stats["retries"],
                          "mismatches": g.stats["mismatches"],
                          "weight": _weight(m)}

        threads = [threading.Thread(target=run_replica, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert set(results) == {0, 1}
        for r in (0, 1):
            assert results[r]["retries"] == 1, results
            assert results[r]["mismatches"] == 1, results
        assert np.array_equal(results[0]["weight"],
                              results[1]["weight"])

    def test_grad_accumulation_replay_is_bitwise_faithful(self):
        """Regression (review finding): a replayed MICROSTEP must not
        double-bank its gradient contribution or shift the micro/apply
        cadence — the accumulation bank and phase counter are part of
        the snapshot set. k=4 on purpose: a k=2 phase error hides
        (2 extra ticks realign mod 2)."""
        import paddle2_tpu.distributed as dist
        batches = _batches(8)

        def run(arm):
            paddle.seed(0)
            m = nn.Linear(8, 4)
            o = dist.shard_optimizer(
                opt.SGD(learning_rate=0.1,
                        parameters=m.parameters()),
                gradient_accumulation_steps=4)
            step = paddle.jit.train_step(
                lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
                reliability=True)
            if arm:
                chaos.arm("poison_loss:3")     # mid-cycle microstep
            for x, y in batches:
                step(x, y)
            step.finalize()
            chaos.disarm()
            return np.asarray(m.weight._data).copy(), step.stats

        w_clean, _ = run(False)
        w_fault, stats = run(True)
        assert stats["retries"] == 1
        assert np.array_equal(w_fault, w_clean)

    def test_zero_sharded_optimizer_composes(self):
        """ZeRO configs inherit the loop from the builder: the
        instrumented program stays bitwise-transparent over the
        sharded step and recovers from an injected NaN."""
        import paddle2_tpu.distributed as dist
        batches = _batches(4)

        def run(reliability, arm=False):
            dist.init_mesh()
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 32), nn.Tanh(),
                                nn.Linear(32, 8))
            o = opt.Adam(learning_rate=1e-2,
                         parameters=net.parameters())
            _, o, _ = dist.group_sharded_parallel(net, o, "os_g")
            step = paddle.jit.train_step(
                lambda x, y: ((net(x) - y) ** 2).mean(), o,
                layers=[net], reliability=reliability)
            if arm:
                chaos.arm("poison_loss:2")
            for x, y in batches:
                x8 = paddle.to_tensor(
                    np.tile(np.asarray(x._data), (1, 1)))
                step(x8, paddle.to_tensor(
                    np.asarray(y._data) @ np.zeros((4, 8),
                                                   np.float32) + 0.1))
            if reliability:
                step.finalize()
            chaos.disarm()
            return np.asarray(net[0].weight._data).copy(), step

        w_plain, _ = run(None)
        w_inst, _ = run(True)
        assert np.array_equal(w_plain, w_inst)
        w_fault, step = run(True, arm=True)
        assert step.stats["retries"] == 1
        assert np.array_equal(w_fault, w_inst)


class TestAMPFused:
    def test_in_program_skip_one_readback(self):
        """poison_grads inside the compiled AMP step: the update is
        skipped IN-PROGRAM (params bitwise unchanged for that step),
        the scale backs off exactly like the eager cycle, no retry is
        burned, and the whole step costs ONE packed readback."""
        batches = _batches(6)
        scaler = GradScaler(init_loss_scaling=2.0 ** 10)
        m, o, step = _build(
            reliability=ReliabilityConfig(scaler=scaler))
        chaos.arm("poison_grads:3")
        s0 = numerics.host_sync_count()
        losses = [float(step(x, y)) for x, y in batches]
        step.finalize()
        syncs = numerics.host_sync_count() - s0
        chaos.disarm()
        assert syncs == len(batches)           # exactly one per step
        assert step.stats["retries"] == 0      # skip, not a failure
        assert all(np.isfinite(l) for l in losses)
        # one skip: scale halved once, step count reflects 5 updates
        assert scaler.get_loss_scaling() == 2.0 ** 9
        assert o._step_count == len(batches) - 1

    def test_matches_eager_scaler_cycle(self):
        """Same fault, eager GradScaler loop: identical skip/backoff
        bookkeeping (the satellite's double-sentinel fix — one flag,
        consumed once, same state machine)."""
        batches = _batches(6)

        def eager():
            m = _mlp()
            o = opt.AdamW(learning_rate=1e-2,
                          parameters=m.parameters())
            scaler = GradScaler(init_loss_scaling=2.0 ** 10)
            chaos.arm("poison_grads:3")
            for x, y in batches:
                loss = ((m(x) - y) ** 2).mean()
                scaler.scale(loss).backward()
                scaler.step(o)
                scaler.update()
                o.clear_grad()
            chaos.disarm()
            return scaler, o

        e_scaler, e_opt = eager()
        scaler = GradScaler(init_loss_scaling=2.0 ** 10)
        m, o, step = _build(
            reliability=ReliabilityConfig(scaler=scaler))
        chaos.arm("poison_grads:3")
        for x, y in batches:
            step(x, y)
        step.finalize()
        chaos.disarm()
        assert scaler.get_loss_scaling() == e_scaler.get_loss_scaling()
        assert scaler._good_steps == e_scaler._good_steps
        assert scaler._consecutive_skips == e_scaler._consecutive_skips
        assert o._step_count == e_opt._step_count

    def test_replayed_amp_step_keeps_ledger_consistent(self):
        """Regression (review finding): a rollback voids the failed
        attempt's aux (never applied to restored state) and the
        accepted replay's aux is still consumed — after a
        poison_loss replay the optimizer step count and scale match a
        clean AMP run."""
        batches = _batches(6)
        scaler = GradScaler(init_loss_scaling=2.0 ** 10)
        m, o, step = _build(
            reliability=ReliabilityConfig(scaler=scaler))
        chaos.arm("poison_loss:3")
        for x, y in batches:
            step(x, y)
        step.finalize()
        chaos.disarm()
        assert step.stats["retries"] == 1
        # every step's update was ultimately applied exactly once
        assert o._step_count == len(batches)
        assert scaler.get_loss_scaling() == 2.0 ** 10
        assert scaler._consecutive_skips == 0


class TestDonationSafety:
    def test_set_state_dict_never_aliases_numpy_snapshot(self):
        """Regression (use-after-donate): restoring a host snapshot
        must COPY every numpy leaf — an aliased leaf becomes a donation
        candidate at the next fused step, and donating it frees the
        snapshot itself, so a second restore of the same step reads
        freed memory."""
        m, o, _ = _build()
        x, y = _batches(1)[0]
        loss = ((m(x) - y) ** 2).mean()
        loss.backward()
        o.step()
        snap = {k: (np.asarray(v._data).copy()
                    if hasattr(v, "_data") else v)
                for k, v in o.state_dict().items()
                if not isinstance(v, (int, float))}
        snap["_step_count"] = o._step_count
        o.set_state_dict(snap)
        for p in o._parameter_list():
            st = o._states.get(id(p))
            if st is None:
                continue
            import jax
            for leaf in jax.tree_util.tree_leaves(st):
                for key, host in snap.items():
                    if isinstance(host, np.ndarray) \
                            and hasattr(leaf, "shape") \
                            and host.shape == tuple(leaf.shape):
                        assert not np.shares_memory(
                            np.asarray(leaf), host), key

    def test_double_restore_around_donating_step(self):
        """The snapshot must survive TWO restores with a donating
        optimizer step between them: attempt 1 restores and runs the
        fused (donated) update before failing again; attempt 2 restores
        from the SAME snapshot. Aliasing anywhere in the restore path
        would read freed buffers here."""
        m, o, _ = _build()
        rel = ReliableStep(m, o, snapshot_every=1, max_retries=3,
                           base_delay=0.0, max_delay=0.0)
        batches = _batches(3)
        calls = {"n": 0}

        def step(x, y):
            loss = ((m(x) - y) ** 2).mean()
            loss.backward()
            o.step()
            o.clear_grad()
            calls["n"] += 1
            if calls["n"] in (2, 3):       # fail AFTER donating
                raise TransientStepError("injected")
            return loss

        for x, y in batches:
            rel.run(step, x, y)
        rel.finalize()
        assert rel.stats["restores"] == 2
        assert rel.stats["retries"] == 2
        # the recovered run matches a clean run bitwise
        m2, o2, _ = _build()
        for x, y in batches:
            loss = ((m2(x) - y) ** 2).mean()
            loss.backward()
            o2.step()
            o2.clear_grad()
        assert np.array_equal(_weight(m), _weight(m2))

    def test_snapshot_alias_fence(self):
        import jax.numpy as jnp
        _assert_host_snapshot([{"w": np.zeros((2, 2))}, 3, "x"])
        with pytest.raises(SnapshotAliasError):
            _assert_host_snapshot([{"w": jnp.zeros((2, 2))}])

    def test_compiled_snapshot_is_host_only(self):
        m, o, step = _build(reliability=True)
        x, y = _batches(1)[0]
        step(x, y)
        assert step._snapshot is not None
        _assert_host_snapshot(step._snapshot)   # must not raise


class TestCompileCacheMTTR:
    @pytest.fixture()
    def _cache_flag(self, tmp_path):
        """A cache of the test's own, every executable written; then the
        cache it found (the run's, ``conftest.py``) under the threshold
        it found — the flag re-reads the environment when it is set."""
        found = paddle.get_flags("FLAGS_compilation_cache_dir")
        with pytest.MonkeyPatch.context() as env:
            env.setenv("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
            paddle.set_flags(
                {"FLAGS_compilation_cache_dir": str(tmp_path / "cache")})
            yield str(tmp_path / "cache")
        paddle.set_flags(found)

    def test_compile_events_recorded(self, tmp_path, _cache_flag,
                                     monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path / "fl"))
        fr = flight_recorder.enable(str(tmp_path / "fl"), rank=0,
                                    install_hooks=False)
        try:
            m, o, step = _build(reliability=True)
            x, y = _batches(1)[0]
            step(x, y)
            step.finalize()
        finally:
            flight_recorder.disable()
        compiles = [ev for ev in fr.events() if ev[2] == "compile"]
        assert compiles and compiles[0][3]["seconds"] > 0
        assert compiles[0][3]["cache_hit"] is False
        events = [json.loads(ln) for ln in
                  open(tmp_path / "fl" / "elastic_events.jsonl")]
        cc = [e for e in events
              if e["kind"] == "elastic.compile_cache"]
        assert cc and cc[0]["hit"] is False and cc[0]["compile_s"] > 0

    def test_mttr_budget_blown_warns(self, tmp_path, monkeypatch,
                                     capsys):
        monkeypatch.setenv("PADDLE_FLIGHT_DIR", str(tmp_path / "fl"))
        m, o, step = _build(
            reliability=ReliabilityConfig(mttr_budget=1e-9))
        x, y = _batches(1)[0]
        step(x, y)
        step.finalize()
        assert "MTTR budget blown by compilation" in \
            capsys.readouterr().err
        events = [json.loads(ln) for ln in
                  open(tmp_path / "fl" / "elastic_events.jsonl")]
        assert any(e["kind"] == "elastic.compile_budget_blown"
                   for e in events)

    def test_mttr_budget_env_inherited(self, monkeypatch):
        monkeypatch.setenv("PADDLE_MTTR_BUDGET", "42.5")
        assert ReliabilityConfig().mttr_budget == 42.5

    @pytest.mark.slow
    def test_warm_cache_restart_is_cheaper(self, tmp_path):
        """Two incarnations of the same worker sharing a persistent
        cache: the respawn's compile+first-step is a cache HIT and
        measurably cheaper — the recompile cost the elastic restart
        path used to pay as pure MTTR."""
        script = tmp_path / "w.py"
        script.write_text(
            "import os, numpy as np\n"
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import paddle2_tpu as paddle\n"
            "import paddle2_tpu.optimizer as opt\n"
            "from paddle2_tpu import nn\n"
            "paddle.seed(0)\n"
            "m = nn.Sequential(nn.Linear(8, 32), nn.ReLU(),"
            " nn.Linear(32, 4))\n"
            "o = opt.AdamW(learning_rate=1e-2,"
            " parameters=m.parameters())\n"
            "step = paddle.jit.train_step("
            "lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],"
            " reliability=True)\n"
            "rs = np.random.RandomState(0)\n"
            "x = paddle.to_tensor(rs.randn(16, 8)"
            ".astype(np.float32))\n"
            "y = paddle.to_tensor(rs.randn(16, 4)"
            ".astype(np.float32))\n"
            "step(x, y); step.finalize()\n")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "PADDLE_", "FLAGS_"))}
        env.update({
            "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
            "PADDLE2_TPU_CACHE_DIR": str(tmp_path / "cache"),
            "PADDLE2_TPU_CACHE_MIN_COMPILE_S": "0",
            "PADDLE_FLIGHT_DIR": str(tmp_path / "fl"),
        })
        for gen in ("0", "1"):
            env["PADDLE_RESTART_GENERATION"] = gen
            subprocess.run([sys.executable, str(script)], env=env,
                           check=True, capture_output=True,
                           timeout=240)
        events = [json.loads(ln) for ln in
                  open(tmp_path / "fl" / "elastic_events.jsonl")]
        cc = [e for e in events
              if e["kind"] == "elastic.compile_cache"]
        assert len(cc) == 2
        assert cc[0]["hit"] is False and cc[0]["generation"] == 0
        assert cc[1]["hit"] is True and cc[1]["generation"] == 1
        assert cc[1]["compile_s"] < cc[0]["compile_s"]


class TestLauncherPlumbing:
    def test_worker_env_cache_and_budget(self, monkeypatch):
        from paddle2_tpu.distributed.launch.main import (_parse,
                                                         _worker_env)
        for k in ("PADDLE2_TPU_CACHE_DIR", "FLAGS_compilation_cache_dir",
                  "JAX_COMPILATION_CACHE_DIR"):
            monkeypatch.delenv(k, raising=False)
        # elastic launchers forward the MTTR budget and invent NO cache
        # directory: the workers' own default is the fixed in-checkout
        # path, on — never a temp-dir/job-id path that cannot hit on a
        # fresh machine
        args = _parse(["--max_restarts", "2", "--mttr_budget", "30",
                       "--job_id", "jobX", "x.py"])
        env = _worker_env(args, 0)
        assert env["PADDLE_MTTR_BUDGET"] == "30.0"
        assert "PADDLE2_TPU_CACHE_DIR" not in env
        # a plain one-shot launch: same
        env = _worker_env(_parse(["x.py"]), 0)
        assert "PADDLE2_TPU_CACHE_DIR" not in env
        # explicit dir is forwarded; 'none' forwards the OFF value
        env = _worker_env(_parse(["--compile_cache_dir", "/o/cache",
                                  "x.py"]), 0)
        assert env["PADDLE2_TPU_CACHE_DIR"] == "/o/cache"
        env = _worker_env(_parse(["--max_restarts", "2",
                                  "--compile_cache_dir", "none",
                                  "x.py"]), 0)
        assert env["PADDLE2_TPU_CACHE_DIR"] == ""

    def test_operator_cache_env_not_clobbered(self, monkeypatch):
        from paddle2_tpu.distributed.launch.main import (_parse,
                                                         _worker_env)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("PADDLE2_TPU_CACHE_DIR", "/operator/choice")
        args = _parse(["--compile_cache_dir", "/o/cache", "x.py"])
        env = _worker_env(args, 0)
        assert env["PADDLE2_TPU_CACHE_DIR"] == "/operator/choice"

    def test_option_yields_to_jax_cache_env(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: the launcher sets no other
        directory, even with an explicit --compile_cache_dir."""
        from paddle2_tpu.distributed.launch.main import (_parse,
                                                         _worker_env)
        monkeypatch.delenv("PADDLE2_TPU_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        env = _worker_env(_parse(["--compile_cache_dir", "/o/cache",
                                  "x.py"]), 0)
        assert "PADDLE2_TPU_CACHE_DIR" not in env
        assert env["JAX_COMPILATION_CACHE_DIR"] == "/placed/outside"

    def test_shared_chips_rejected_on_tpu_host(self, monkeypatch):
        from paddle2_tpu.distributed.launch.main import (
            _parse, _reject_shared_chips)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with pytest.raises(SystemExit, match="nproc_per_node"):
            _reject_shared_chips(_parse(["--nproc_per_node", "4",
                                         "x.py"]))
        _reject_shared_chips(_parse(["--nproc_per_node", "1", "x.py"]))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        _reject_shared_chips(_parse(["--nproc_per_node", "4", "x.py"]))


@pytest.mark.slow
@pytest.mark.gang
class TestCompiledGangDrill:
    def test_kill_respawn_adopts_replica_through_compiled_step(
            self, tmp_path):
        """2-rank drill THROUGH the compiled step: chaos SIGKILLs rank
        1 mid-run, the launcher rescales to world 1, and the respawned
        worker resumes the instrumented jit.train_step from the buddy
        replica — then keeps training through the same compiled path,
        with the respawn's recompile accounted in the elastic stream
        (auto-enabled persistent cache)."""
        replica = tmp_path / "shm"
        out = tmp_path / "result.json"
        script = tmp_path / "train.py"
        script.write_text(f"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle2_tpu as paddle
import paddle2_tpu.nn as nn
import paddle2_tpu.optimizer as opt
from paddle2_tpu.distributed import fault_tolerance as ft

rank = int(os.environ.get("PADDLE_TRAINER_ID", 0))
world = int(os.environ.get("PADDLE_TRAINERS_NUM", 1))
restart = int(os.environ.get("PADDLE_ELASTIC_RESTART_COUNT", 0))

paddle.seed(0)
m = nn.Linear(4, 1)
o = opt.SGD(learning_rate=0.05, parameters=m.parameters())
rep = ft.BuddyReplicator(store_dir=os.environ["PADDLE_REPLICA_DIR"])
step = paddle.jit.train_step(
    lambda x, y: ((m(x) - y) ** 2).mean(), o, layers=[m],
    reliability=ft.ReliabilityConfig(snapshot_every=1,
                                     replicator=rep))
resumed = step.resume_from_replica()
start = 0 if resumed is None else resumed
rs = np.random.RandomState(0)
W = np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32)
losses = []
for s in range(start, 12):
    if world > 1:
        time.sleep(0.25)
    if world > 1 and rank == 1 and s == 3:
        # the kill comes inside this step: the victim leaves only once
        # the survivor's replica of step 3 is there to be adopted, so
        # the resume step does not depend on which rank the host's load
        # (or a warm compile cache) let run ahead
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                if rep.fetch(0)["step"] >= 3:
                    break
            except ft.ReplicaUnavailableError:
                pass
            time.sleep(0.05)
    x = paddle.to_tensor(rs.randn(16, 4).astype(np.float32))
    y = paddle.to_tensor(np.asarray(x._data) @ W)
    losses.append(float(np.asarray(step(x, y)._data)))
step.finalize()
if rank == 0:
    json.dump({{"world": world, "restart": restart,
               "resumed": resumed, "losses": losses}},
              open({str(repr(str(out)))}, "w"))
""")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "PADDLE_", "FLAGS_"))}
        env.update({
            "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
            "PADDLE_REPLICA_DIR": str(replica),
            "PADDLE_FLIGHT_DIR": str(tmp_path / "flight"),
            "PADDLE2_TPU_CACHE_MIN_COMPILE_S": "0",
            "FLAGS_chaos": "kill_rank:4:1",
        })
        proc = subprocess.run(
            [sys.executable, "-m", "paddle2_tpu.distributed.launch",
             "--nproc_per_node", "2", "--max_restarts", "2",
             "--elastic_rescale", "--mttr_budget", "300",
             "--compile_cache_dir", str(tmp_path / "cache"),
             str(script)],
            env=env, capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "scale-in: world 2 -> 1" in proc.stderr
        res = json.load(open(out))
        assert res["world"] == 1
        assert res["restart"] >= 1
        assert res["resumed"] is not None and res["resumed"] >= 3
        assert res["losses"][-1] < res["losses"][0]
        events = [json.loads(ln) for ln in
                  open(tmp_path / "flight" / "elastic_events.jsonl")]
        kinds = {e["kind"] for e in events}
        assert "elastic.respawn" in kinds
        assert "elastic.scale_in" in kinds
        assert "elastic.restart_latency" in kinds
        # compile time is part of the MTTR ledger now: every
        # incarnation recorded its build, and the respawn (which found
        # the survivors' warm cache) hit
        cc = [e for e in events
              if e["kind"] == "elastic.compile_cache"]
        assert cc, "no compile accounting in the elastic stream"
        assert any(e["hit"] for e in cc
                   if e.get("generation", 0) >= 1)
