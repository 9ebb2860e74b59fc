"""What every drill shares: stderr logging, the session scratch dir for
metric/trace streams, and the writer of the ``*_r01.json`` artifacts
under ``bench/artifacts/``.
"""

import json
import os
import sys

ARTIFACT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts")


def artifact_path(filename):
    """Where a drill's artifact is written: the one directory that also
    holds the committed copies (``bench/README.md`` says what those are
    for)."""
    return os.path.join(ARTIFACT_DIR, filename)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


_SCRATCH_ROOT = None


def bench_scratch(name, env_var=None):
    """Scratch directory for a bench lane's metric/trace streams.

    An explicit ``env_var`` override wins (CI pins stable names so it
    can diff base-vs-cand streams across two invocations); otherwise
    the lane lands under ONE session tempdir that is removed at exit —
    bench runs must never litter the repo root with ``_bench_*``
    droppings (ISSUE 14 satellite)."""
    if env_var:
        override = os.environ.get(env_var)
        if override:
            return override
    global _SCRATCH_ROOT
    if _SCRATCH_ROOT is None:
        import atexit
        import shutil
        import tempfile
        _SCRATCH_ROOT = tempfile.mkdtemp(prefix="paddle2_bench_")
        atexit.register(shutil.rmtree, _SCRATCH_ROOT,
                        ignore_errors=True)
    return os.path.join(_SCRATCH_ROOT, name)


def write_artifact(path, result, indent=2, sort_keys=False,
                   trailing_newline=False):
    """Write the lane artifact; an unwritable directory (read-only CI
    mount) is tolerated because the stdout JSON line already carries
    the result."""
    try:
        with open(path, "w") as f:
            f.write(json.dumps(result, indent=indent,
                               sort_keys=sort_keys))
            if trailing_newline:
                f.write("\n")
    except OSError:
        return False
    return True
