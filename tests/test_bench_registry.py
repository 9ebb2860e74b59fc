"""The drill registry behind ``bench.py``: every flag is one registered
scenario with declared gates, and the runner holds a build to them."""

import json

import pytest

from bench import artifact
from bench.scenarios import registry

# every flag bench.py's argv ladder accepted before the registry became
# its command line (PR 28)
PARENT_FLAGS = (
    "tracing", "single-chip-speed", "serving-throughput",
    "serving-reliability", "fleet-kv", "million-user-day",
    "ps-recommender", "moe-training", "long-context", "serving",
    "multichip-scaling", "inject-fault", "guardrails", "flight-recorder",
    "sdc", "reliable-step", "observability", "elastic")


@pytest.mark.parametrize("name", PARENT_FLAGS)
def test_every_drill_flag_is_a_registered_scenario_with_gates(name):
    sc = registry.get(name)
    assert sc.name == name
    assert sc.gates, f"--{name} declares no gate"


@pytest.mark.parametrize("result,gates,rc", [
    ({"gates": {"a": True, "undeclared": False}}, ("a",), 1),
    ({"gates": {"a": True}}, ("a",), 0),
    ({"ok": True, "value": 0}, ("ok",), 0),      # top-level verdict:
    ({"ok": False, "value": 1}, ("ok",), 1),     # only declared keys gate
    ({"gates": {"a": True}}, ("a", "b"), KeyError),
    ({"value": 1}, ("ok",), KeyError),
])
def test_runner_holds_the_build_to_its_declared_gates(
        monkeypatch, tmp_path, capsys, result, gates, rc):
    monkeypatch.setattr(artifact, "ARTIFACT_DIR", str(tmp_path))
    sc = registry.Scenario(name="probe", artifact="PROBE_r01.json",
                           build=lambda scenario: result, gates=gates)
    monkeypatch.setitem(registry.REGISTRY, "probe", sc)
    if rc is KeyError:
        with pytest.raises(KeyError, match="never evaluated"):
            registry.run("probe")
        return
    assert registry.run("probe") == rc
    assert json.loads(capsys.readouterr().out) == result
    assert json.load(open(tmp_path / "PROBE_r01.json")) == result
