"""paddle.distributed.spawn (reference distributed/spawn.py:463) —
launch ``func`` in ``nprocs`` worker processes with the launcher's env
contract (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_MASTER), the
programmatic twin of ``python -m paddle2_tpu.distributed.launch``.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
import tempfile
from typing import Any, Iterable

__all__ = ["spawn"]

_WORKER_SNIPPET = """\
import pickle, sys
with open(sys.argv[1], "rb") as f:
    func, args = pickle.load(f)
func(*args)
"""


class MultiprocessContext:
    def __init__(self, procs):
        self.processes = procs

    def join(self, timeout=None):
        rcs = [p.wait(timeout=timeout) for p in self.processes]
        bad = [i for i, rc in enumerate(rcs) if rc != 0]
        if bad:
            raise RuntimeError(
                f"spawn worker(s) {bad} exited nonzero: "
                f"{[rcs[i] for i in bad]}")
        return True


def spawn(func, args: Iterable[Any] = (), nprocs: int = -1,
          join: bool = True, daemon: bool = False, **options):
    """Pickle (func, args) and exec one Python per rank with the
    collective env set. Workers call dist.init_parallel_env() themselves,
    exactly as under the CLI launcher."""
    if nprocs < 1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", 1))
    # a chip belongs to one process at a time (and a parent that has
    # touched JAX holds it): several workers on a TPU host would fail
    # or hang against each other — same rule as the CLI launcher
    from ..flags import _env_platform
    platform = str(options.get("env", {}).get("JAX_PLATFORMS", "")) \
        or _env_platform(os.environ)
    if nprocs > 1 and platform.lower().startswith("tpu"):
        raise RuntimeError(
            f"spawn(nprocs={nprocs}) on a TPU host: every worker would "
            "claim the same chips. Drive the host's chips from one "
            "process (a mesh over jax.devices()), or pass "
            "env={'JAX_PLATFORMS': 'cpu'} for a CPU gang.")
    with tempfile.NamedTemporaryFile("wb", suffix=".pkl",
                                     delete=False) as f:
        pickle.dump((func, tuple(args)), f)
        payload = f.name
    # the worker unpickles by importing func's module: make sure that
    # module's directory (and the caller's cwd) resolve there
    import inspect
    extra_paths = [os.getcwd()]
    try:
        extra_paths.append(os.path.dirname(inspect.getfile(func)))
    except TypeError:
        pass
    pypath = os.pathsep.join(
        extra_paths + [os.environ.get("PYTHONPATH", "")])
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for rank in range(nprocs):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_MASTER": f"127.0.0.1:{port}",
            "PADDLE_LOCAL_RANK": str(rank),
            "PYTHONPATH": pypath,
        })
        env.update({str(k): str(v) for k, v in options.get("env",
                                                           {}).items()})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER_SNIPPET, payload], env=env))
    ctx = MultiprocessContext(procs)
    if join:
        ctx.join()
    return ctx
