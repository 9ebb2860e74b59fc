"""Declarative drill registry: every ``bench.py --<name>`` is one entry.

A scenario DECLARES what it is — model, parallelism, trace shape, the
gate names it must satisfy, the streams it emits — and the runner
supplies what the lanes used to hand-roll: the stdout JSON line, the
artifact under ``bench/artifacts/``, the gate verdict as exit code.
The metric/trace streams land in env-overridable scratch dirs so two
runs can be diffed with perf_doctor/serve_doctor.

The builder receives its :class:`Scenario` and returns the result
dict. Every DECLARED gate name must be in it — in its ``"gates"``
mapping or, for the lanes whose verdict is a top-level key (``"ok"``,
``"recovered"``), at the top level — so a scenario whose declaration
drifts from its implementation fails loudly, not silently.
"""

import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Tuple

from ..artifact import artifact_path, log, write_artifact


@dataclass(frozen=True)
class Scenario:
    """One declarative bench lane."""

    name: str                     # registry key; CLI flag is --<name>
    artifact: str                 # filename under bench/artifacts/;
    #                               "" = the lane keeps none
    build: Callable[["Scenario"], Dict[str, Any]]
    description: str = ""
    model: Dict[str, Any] = field(default_factory=dict)
    parallelism: Dict[str, Any] = field(default_factory=dict)
    trace: Dict[str, Any] = field(default_factory=dict)
    gates: Tuple[str, ...] = ()   # declared gate names (must all exist)
    streams: Dict[str, str] = field(default_factory=dict)
    # stream role -> env var that pins its directory (CI diffing)
    deterministic: bool = True
    # False: the result holds host-clock readings, so two runs' artifacts
    # differ by design and CI does not cmp them
    writes_own_artifact: bool = False
    # True: the build wrote `artifact` itself (a payload other than the
    # stdout result), so the runner writes nothing


REGISTRY: Dict[str, Scenario] = {}


def register(scenario: Scenario) -> Scenario:
    if scenario.name in REGISTRY:
        raise ValueError(f"duplicate scenario {scenario.name!r}")
    REGISTRY[scenario.name] = scenario
    return scenario


def get(name: str) -> Scenario:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown scenario {name!r}; "
                       f"registered: {sorted(REGISTRY)}") from None


def run(name: str) -> int:
    """Build the scenario's result, print it as one JSON line, write
    its artifact; the process exit code is the gate verdict."""
    sc = get(name)
    result = sc.build(sc)
    gates = result.get("gates")
    if gates is None:     # the verdict is top-level keys of the result
        gates = {g: result[g] for g in sc.gates if g in result}
    missing = [g for g in sc.gates if g not in gates]
    if missing:
        raise KeyError(f"scenario {sc.name!r} declared gates the "
                       f"builder never evaluated: {missing}")
    print(json.dumps(result))
    if sc.artifact and not sc.writes_own_artifact:
        write_artifact(artifact_path(sc.artifact), result)
    failed = {g: v for g, v in gates.items() if not v}
    if failed:
        log(f"{sc.name}: GATE FAILURE {failed}")
        return 1
    log(f"{sc.name}: all gates passed")
    return 0
