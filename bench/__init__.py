"""The drills behind ``bench.py`` (the registry's command line).

- :mod:`bench.artifact` — stderr logging, the session scratch dir and
  the one artifact writer.
- :mod:`bench.scenarios` — the scenario registry: a scenario declares
  model + parallelism + trace + gates, the runner supplies artifact
  emission and gate evaluation.
- ``bench/artifacts/`` — the artifacts the drills write; ``README.md``
  here says what the committed copies are for.
"""

from .artifact import artifact_path, bench_scratch, log, write_artifact

__all__ = ["artifact_path", "bench_scratch", "log", "write_artifact"]
