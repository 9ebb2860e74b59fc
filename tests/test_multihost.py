"""Multi-host bootstrap e2e (reference test-style: spawn localhost
subprocesses with env-var rendezvous, test_parallel_dygraph_dataparallel
start_local_trainers pattern).

Two CPU processes rendezvous through the JAX coordination service (the
TCPStore analog, parallel.py:1134), form ONE 2-process global mesh, and
run a real cross-process all_reduce.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

# full models / spawned processes; `gang` selects the multiprocess
# suite (pytest -m gang) alongside the launcher drills
pytestmark = [pytest.mark.slow, pytest.mark.gang]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import sys
    import jax
    # the workers are a CPU gang: pin the platform before any backend
    # use (see conftest.py)
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle2_tpu as paddle
    import paddle2_tpu.distributed as dist

    dist.init_parallel_env()
    rank = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    assert dist.world_size() == 2, dist.world_size()
    # each process contributes ITS tensor; both must see the sum
    t = paddle.to_tensor(np.array([float(rank + 1)] * 4, np.float32))
    dist.all_reduce(t)
    np.testing.assert_allclose(t.numpy(), np.full(4, 3.0))
    # broadcast from rank 0
    b = paddle.to_tensor(np.array([float(rank)] * 4, np.float32))
    dist.broadcast(b, src=0)
    np.testing.assert_allclose(b.numpy(), np.zeros(4))
    # all_gather (list form)
    outs = []
    dist.all_gather(outs, paddle.to_tensor(
        np.array([float(rank)], np.float32)))
    np.testing.assert_allclose(
        np.concatenate([o.numpy() for o in outs]), [0.0, 1.0])
    # reduce_scatter: local [2] rows, reduced then split
    rs = paddle.to_tensor(np.array([1.0, 2.0], np.float32) * (rank + 1))
    dist.reduce_scatter(rs, rs)
    np.testing.assert_allclose(rs.numpy(), [3.0] if rank == 0 else [6.0])
    # all_to_all
    ins = [paddle.to_tensor(np.array([float(rank * 10 + j)], np.float32))
           for j in range(2)]
    outs2 = []
    dist.all_to_all(outs2, ins)
    np.testing.assert_allclose(
        np.concatenate([o.numpy() for o in outs2]),
        [float(rank), float(10 + rank)])
    # scatter from rank 1
    sc = paddle.to_tensor(np.zeros(3, np.float32))
    lst = ([paddle.to_tensor(np.full(3, float(i + 1), np.float32))
            for i in range(2)] if rank == 1 else None)
    dist.scatter(sc, lst, src=1)
    np.testing.assert_allclose(sc.numpy(), np.full(3, float(rank + 1)))
    dist.barrier()
    print(f"RANK{rank}_OK", flush=True)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _base_env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "PADDLE_", "XLA_FLAGS"))}
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    })
    return env


def test_two_process_bootstrap_and_all_reduce(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    procs = []
    for r in range(2):
        env = _base_env()
        env.update({
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(r),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=240)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-2000:]}"
        assert f"RANK{r}_OK" in out


def test_launcher_forms_global_mesh(tmp_path):
    """python -m paddle2_tpu.distributed.launch --master ... spawns the
    gang, wires the rendezvous env, and shuts down cleanly."""
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle2_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}", "--nproc_per_node", "2",
         "--log_dir", str(tmp_path / "logs"), str(script)],
        env=_base_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    logs = ""
    logdir = tmp_path / "logs"
    if logdir.exists():
        for f in logdir.iterdir():
            logs += f.read_text()
    blob = logs + proc.stdout + proc.stderr
    assert "RANK0_OK" in blob and "RANK1_OK" in blob, blob[-2000:]


ASYNC_CKPT_WORKER = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle2_tpu as paddle
    import paddle2_tpu.distributed as dist
    import paddle2_tpu.distributed.checkpoint as dck

    dist.init_parallel_env()
    rank = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    ckpt = sys.argv[1]

    # global [4, 8] tensor sharded over the 2-process mesh: each process
    # holds 2 rows
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    mesh = dist.get_mesh()
    vals = np.arange(32, dtype=np.float32).reshape(4, 8)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(mesh.axis_names[0])),
        vals[rank * 2:(rank + 1) * 2])
    t = paddle.to_tensor(np.zeros((4, 8), np.float32))
    t._data = arr
    state = {"w": t, "step": 3}

    # ASYNC save: both processes run the barriered write phase on their
    # background threads; wait() makes it durable everywhere
    h = dck.save_state_dict(state, ckpt, async_save=True)
    assert h is not None
    h.wait()

    # immediately save AGAIN (serializes on the global pending registry)
    state["step"] = 4
    h2 = dck.save_state_dict(state, ckpt, async_save=True)
    h2.wait()

    # reload on the same mesh and verify both value and step
    t2 = paddle.to_tensor(np.zeros((4, 8), np.float32))
    t2._data = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(mesh.axis_names[0])),
        np.zeros((2, 8), np.float32))
    tgt = {"w": t2, "step": 0}
    dck.load_state_dict(tgt, ckpt)
    got = np.asarray(jax.experimental.multihost_utils
                     .process_allgather(t2._data, tiled=True))
    np.testing.assert_allclose(got.reshape(4, 8), vals)
    assert tgt["step"] == 4
    print(f"RANK{rank}_CKPT_OK", flush=True)
""")


def test_two_process_async_checkpoint(tmp_path):
    """Async save's barriered write phase across REAL processes: shard
    files from both ranks land under one committed metadata, back-to-back
    saves serialize, reload reassembles the global value."""
    import jax.experimental.multihost_utils  # noqa: F401 (worker uses it)
    script = tmp_path / "worker.py"
    script.write_text(ASYNC_CKPT_WORKER)
    ckpt = str(tmp_path / "ckpt")
    port = _free_port()
    procs = []
    for r in range(2):
        env = _base_env()
        env.update({
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(r),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script), ckpt], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=240)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-2000:]}"
        assert f"RANK{r}_CKPT_OK" in out
    # exactly one committed uid's shard files remain (uid 1, the resave)
    import os as _os
    files = sorted(f for f in _os.listdir(ckpt) if f.startswith("data_"))
    assert files == ["data_1_0.pkl", "data_1_1.pkl"], files
