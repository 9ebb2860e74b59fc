"""Nothing on the main path can make a missing chip look like a pass:
device queries propagate, a TPU place is never served by a CPU device,
peaks are looked up and not assumed, and the compile cache has one
placement rule."""

import os
import subprocess
import sys

import jax
import pytest

import paddle2_tpu as paddle
from paddle2_tpu.framework import core
from paddle2_tpu.kernels import _platform
from paddle2_tpu.observability import cost_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


# ------------------------------------------------------------- platform
def test_cpu_backend_interprets_and_is_not_tpu():
    assert _platform.device_platform() == "cpu"
    assert _platform.interpret_default() is True
    assert _platform.on_tpu() is False


def test_device_query_error_propagates(monkeypatch):
    """A process that cannot reach its device fails; it does not
    quietly interpret a kernel or take a reference path."""
    from paddle2_tpu.kernels.attention import use_pallas

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", boom)
    for query in (_platform.interpret_default, _platform.on_tpu,
                  lambda: use_pallas((8, 1024, 16, 64))):
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            query()


@pytest.mark.parametrize("platform", ["gpu", "rocm", "METAL"])
def test_unknown_platform_is_an_error(monkeypatch, platform):
    monkeypatch.setattr(jax, "devices", lambda: [_Dev(platform, "x")])
    with pytest.raises(RuntimeError, match="unsupported JAX platform"):
        _platform.interpret_default()


def test_tpu_platform_compiles(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_Dev("tpu", "TPU v5 lite")])
    assert _platform.interpret_default() is False
    assert _platform.on_tpu() is True


# --------------------------------------------------------------- places
def test_tpu_place_raises_on_cpu_only_backend():
    with pytest.raises(RuntimeError, match="no 'tpu' device"):
        core.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="no 'gpu' device"):
        core.CUDAPlace(0).jax_device()
    assert core.CPUPlace(0).jax_device().platform == "cpu"
    assert core.device_count("tpu") == 0


def test_set_device_tpu_then_to_tensor_raises():
    prev = paddle.get_device()
    try:
        paddle.set_device("tpu")
        with pytest.raises(RuntimeError, match="no 'tpu' device"):
            paddle.to_tensor([1.0, 2.0])
    finally:
        paddle.set_device(prev)
    assert paddle.to_tensor([1.0]).shape == [1]


def test_platform_matches_tpu_only():
    assert core._platform_matches(_Dev("tpu", ""), "tpu")
    assert core._platform_matches(_Dev("tpu", ""), "gpu")   # API alias
    assert not core._platform_matches(_Dev("gpu", ""), "tpu")
    assert not core._platform_matches(_Dev("cpu", ""), "tpu")


# ---------------------------------------------------------------- peaks
def test_cost_model_known_unknown_and_cpu(monkeypatch):
    for k in (cost_model.PEAK_ENV, cost_model.HBM_ENV,
              "PADDLE_HBM_CAPACITY_GB"):
        monkeypatch.delenv(k, raising=False)
    peak, hbm, label = cost_model.chip_peak(_Dev("tpu", "TPU v5 lite"))
    assert (peak, hbm, label) == (197e12, 819e9, "v5 lite")
    assert cost_model.chip_hbm_gb(_Dev("tpu", "TPU v5 lite")) == 16.0
    with pytest.raises(RuntimeError, match="not in cost_model"):
        cost_model.chip_peak(_Dev("tpu", "TPU v9 mystery"))
    with pytest.raises(RuntimeError, match="not in cost_model"):
        cost_model.chip_hbm_gb(_Dev("tpu", "TPU v9 mystery"))
    # the CPU nominal entry stays (virtual-clock drills)
    assert cost_model.chip_peak(_Dev("cpu", "cpu"))[2].startswith(
        "cpu-nominal")
    assert cost_model.chip_hbm_gb(_Dev("cpu", "cpu")) == 16.0
    # and so does the explicit override
    monkeypatch.setenv(cost_model.PEAK_ENV, "100")
    monkeypatch.setenv(cost_model.HBM_ENV, "500")
    assert cost_model.chip_peak(_Dev("tpu", "TPU v9 mystery")) == (
        100e12, 500e9, "env-override")


def test_bench_chip_peak_and_device_lanes(monkeypatch):
    import importlib.util
    from paddle2_tpu.observability import cost_model
    # bench.py the script, not the bench/ package next to it
    spec = importlib.util.spec_from_file_location(
        "bench_script", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    monkeypatch.syspath_prepend(REPO)
    spec.loader.exec_module(bench)
    # the one peaks table the lane reads refuses a chip it does not know
    with pytest.raises(RuntimeError, match="CHIP_PEAKS"):
        cost_model.chip_peak(_Dev("tpu", "TPU v9 mystery"))
    assert cost_model.chip_peak(_Dev("tpu", "TPU v5 lite"))[::2] == (
        197e12, "v5 lite")
    with pytest.raises(SystemExit, match="measures a TPU chip"):
        bench.bench_resnet50()


def test_mem_stats_does_not_swallow(monkeypatch):
    from paddle2_tpu import device

    class Broken:
        def memory_stats(self):
            raise RuntimeError("device lost")
    monkeypatch.setattr(device, "_device_of", lambda d=None: Broken())
    with pytest.raises(RuntimeError, match="device lost"):
        device.memory_allocated()


def test_dead_fallback_flag_is_gone():
    with pytest.raises(ValueError, match="unknown flag"):
        paddle.get_flags("FLAGS_enable_api_kernel_fallback")


# ---------------------------------------------------------- compile cache
_RESOLVE = (
    "import jax, paddle2_tpu\n"
    "from paddle2_tpu.flags import compile_cache_dir\n"
    "print(compile_cache_dir())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _resolve(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "PADDLE2_TPU_CACHE_DIR",
                        "FLAGS_compilation_cache_dir")}
    env.pop("JAX_PLATFORMS", None)     # import only: no backend starts
    env.update({"PYTHONPATH": REPO}, **env_extra)
    out = subprocess.run([sys.executable, "-c", _RESOLVE], env=env,
                         check=True, capture_output=True, text=True,
                         cwd="/", timeout=120).stdout.split("\n")
    return out[0], out[1]


def test_cache_default_is_one_fixed_path_in_the_checkout():
    """Unset: <checkout>/.jax_cache, on — and two processes agree (no
    tempfile, pid, job id or time in the path)."""
    first, second = _resolve({}), _resolve({})
    fixed = os.path.join(REPO, ".jax_cache")
    assert first == second == (fixed, fixed)


def test_cache_env_var_wins_and_nothing_else_is_set(tmp_path):
    """JAX_COMPILATION_CACHE_DIR: JAX reads it itself (its config
    already holds it) and repo code sets no other directory — not even
    when the repo's own override is also exported."""
    placed = str(tmp_path / "placed")
    for extra in ({}, {"PADDLE2_TPU_CACHE_DIR": "/should/not/win"},
                  {"FLAGS_compilation_cache_dir": "/nor/this"}):
        used, configured = _resolve(
            dict(extra, JAX_COMPILATION_CACHE_DIR=placed))
        assert (used, configured) == (placed, placed)


def test_python_bench_py_prints_no_figure_without_a_chip():
    r = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "tokens_per_sec" not in r.stdout
    assert "measures a TPU chip" in r.stderr


def test_chip_smoke_refuses_without_a_chip():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a tpu device" in r.stderr


def test_cache_env_var_never_updates_jax_config(monkeypatch):
    """With the env var set, neither import nor set_flags updates
    ``jax_compilation_cache_dir``."""
    from paddle2_tpu import flags
    seen = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (seen.append(k), real(k, v))[1])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    flags._apply_compilation_cache("/somewhere/else")
    assert seen and "jax_compilation_cache_dir" not in seen
    assert flags.compile_cache_dir() == "/placed/outside"
    seen.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    flags._apply_compilation_cache("")
    assert "jax_compilation_cache_dir" in seen
    # that call switched the cache off: hand the run's back (conftest.py)
    flags._apply_compilation_cache(flags.compile_cache_dir())


def test_cache_repo_override_and_off():
    assert _resolve({"PADDLE2_TPU_CACHE_DIR": "/o/cache"}) == (
        "/o/cache", "/o/cache")
    assert _resolve({"FLAGS_compilation_cache_dir": ""}) == ("", "None")
    assert _resolve({"PADDLE2_TPU_CACHE_DIR": ""}) == ("", "None")
    # the backend does not enter into it: on by default on the CPU too
    fixed = os.path.join(REPO, ".jax_cache")
    assert _resolve({"JAX_PLATFORMS": "cpu"}) == (fixed, fixed)


# ------------------------------------------- the smoke's check can fail
def _smoke_case(fault):
    """Four requests x three served tokens over a 64-way vocabulary:
    reference logits whose maximum (2.5: one bf16 step is 2^-6) sits
    on the served token, then one fault."""
    import numpy as np
    sys.path.insert(0, REPO)
    import chip_smoke
    rng = np.random.default_rng(0)
    streams = [[int(t) for t in rng.choice(64, 3, replace=False)]
               for _ in range(4)]
    refs = []
    for s in streams:
        ref = rng.uniform(-1.0, 1.0, (3, 64)).astype(np.float32)
        for k, tok in enumerate(s):
            ref[k, tok] = 2.5
        refs.append(ref)
    by_generate = [list(s) for s in streams]
    step = chip_smoke.bf16_ulp(2.5)
    assert step == 2.0 ** -6
    other = next(t for t in range(64) if t != streams[1][1])
    if fault == "tie_with_generate":       # parts at a 2-step tie: fine
        refs[1][1, other] = 2.5 - 2 * step
        by_generate[1][1] = other
    elif fault == "served_token_off_the_maximum":
        refs[1][1, other] = 2.5 + (chip_smoke.TIE_ULPS + 1) * step
    elif fault == "parts_from_generate_without_a_tie":
        by_generate[1][1] = other          # reference logit ~0: no tie
    elif fault == "streams_do_not_depend_on_the_prompt":
        streams = [streams[0]] * 4
        refs = [refs[0]] * 4
        by_generate = [streams[0]] * 4
    elif fault == "control_cannot_tell_prompts_apart":
        for ref in refs:                   # every stream fits every ref
            for s in streams:
                for k, tok in enumerate(s):
                    ref[k, tok] = 2.5
    return chip_smoke.check_streams, (refs, streams, by_generate)


@pytest.mark.parametrize("fault", [
    "served_token_off_the_maximum", "parts_from_generate_without_a_tie",
    "streams_do_not_depend_on_the_prompt",
    "control_cannot_tell_prompts_apart"])
def test_chip_smoke_served_token_check_fails_on(fault):
    check, args = _smoke_case(fault)
    with pytest.raises(AssertionError):
        check(*args)


def test_chip_smoke_served_token_check_passes_clean_and_at_a_tie():
    check, args = _smoke_case(None)
    out = check(*args)
    assert out["tokens_equal_generate"] == 12
    assert out["worst_below_reference_max_ulps"] == 0.0
    check, args = _smoke_case("tie_with_generate")
    out = check(*args)
    assert out["tokens_equal_generate"] == 10    # 3 + 1 + 3 + 3
    assert out["ties_where_generate_parts"][0]["generate_below_max_ulps"] \
        == 2.0
