"""paddle.inference (reference paddle/fluid/inference/api/
paddle_inference_api.h:53 Config/Predictor contract).

TPU-native inference engine: the artifact is the StableHLO program that
jit.save exports (.pdmodel + .pdiparams); Predictor wraps the deserialized
executable. The reference's GPU/TensorRT/MKLDNN toggles are accepted and
recorded but inert — XLA owns codegen on TPU.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Config", "Predictor", "create_predictor", "PredictorPool",
           "get_version"]


class Config:
    """AnalysisConfig parity (inference_api.cc Config)."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._prefix = prog_file
        self._params_file = params_file          # explicit path wins
        self._use_gpu = False
        self._device_id = 0
        self._cpu_math_threads = 1
        self._memory_optim = True
        self._ir_optim = True
        self._switches: Dict[str, bool] = {}
        self._serving: Optional[dict] = None

    # -- model location --------------------------------------------------
    def set_model(self, prog_file: str, params_file: Optional[str] = None):
        self._prefix = prog_file[:-len(".pdmodel")] \
            if prog_file.endswith(".pdmodel") else prog_file
        # an explicit params_file is honored verbatim (the reference
        # contract — weights may live under a different prefix than the
        # program); omitting it falls back to prefix-derived
        self._params_file = params_file

    def model_dir(self):
        return os.path.dirname(self._prefix or "")

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def params_file(self):
        if self._params_file:
            return self._params_file
        return (self._prefix or "") + ".pdiparams"

    # -- serving (paddle2_tpu.serving integration) -----------------------
    def enable_continuous_batching(self, **engine_kwargs):
        """Route this config to the continuous-batching
        :class:`~paddle2_tpu.serving.ServingEngine` instead of the
        one-request-at-a-time Predictor. ``engine_kwargs`` are
        :class:`~paddle2_tpu.serving.EngineConfig` fields (block_size,
        num_blocks, max_batch, weight_only_int8, ...). Build the
        engine with :meth:`create_serving_engine` — it needs the model's
        architecture config (a ``GPTConfig`` or an ``Lfm2MoeConfig``),
        which the serialized artifact does not carry."""
        self._serving = dict(engine_kwargs)

    def continuous_batching_enabled(self) -> bool:
        return self._serving is not None

    def create_serving_engine(self, gpt_config):
        """The continuous-batching engine over this artifact.
        ``gpt_config`` is the architecture config object of a family the
        engine serves — ``GPTConfig`` or ``Lfm2MoeConfig`` (the keyword
        is older than the second family); the engine rebuilds the model
        from it and refuses at construction the features that family
        does not have yet."""
        from .serving import EngineConfig, ServingEngine
        if self._serving is None:
            raise ValueError("call enable_continuous_batching() first")
        return ServingEngine(artifact_path=self._prefix,
                             artifact_params_path=self.params_file(),
                             gpt_config=gpt_config,
                             config=EngineConfig(**self._serving))

    # -- device knobs (recorded; XLA decides on TPU) ---------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_gpu = True
        self._device_id = device_id

    def disable_gpu(self):
        self._use_gpu = False

    def use_gpu(self):
        return self._use_gpu

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_math_threads = n

    def enable_memory_optim(self, flag=True):
        self._memory_optim = flag

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def enable_tensorrt_engine(self, *a, **k):
        self._switches["tensorrt"] = True  # inert on TPU

    def enable_mkldnn(self):
        self._switches["mkldnn"] = True  # inert on TPU

    def summary(self):
        return {"model": self._prefix, "use_gpu": self._use_gpu,
                "switches": dict(self._switches)}


class _IOTensor:
    """PaddleTensor-ish handle (copy_from_cpu / copy_to_cpu contract)."""

    def __init__(self, owner: "Predictor", name: str, is_input: bool):
        self._owner = owner
        self.name = name
        self._is_input = is_input

    def copy_from_cpu(self, arr: np.ndarray):
        self._owner._feed[self.name] = np.asarray(arr)

    def reshape(self, shape):
        pass  # shapes flow from the fed array

    def copy_to_cpu(self) -> np.ndarray:
        return self._owner._fetch[self.name]


class Predictor:
    """paddle_infer::Predictor parity over a TranslatedLayer."""

    def __init__(self, config: Config):
        from .jit.api import load as jit_load
        if not os.path.exists(config.prog_file()):
            raise ValueError(
                f"no program at {config.prog_file()}; produce it with "
                "paddle.jit.save(layer, path, input_spec=[...])")
        # honor an explicitly-set params file (set_model's second arg)
        self._loaded = jit_load(config._prefix,
                                params_path=config.params_file())
        self._config = config
        self._n_inputs = None
        self._feed: Dict[str, np.ndarray] = {}
        self._fetch: Dict[str, np.ndarray] = {}

    def get_input_names(self) -> List[str]:
        n = self._n_inputs
        if n is None:
            try:
                n = len(self._loaded._exported.in_avals[1])
            except Exception:
                n = 1
            self._n_inputs = n
        return [f"x{i}" for i in range(n)]

    def get_input_handle(self, name: str) -> _IOTensor:
        return _IOTensor(self, name, True)

    def get_output_names(self) -> List[str]:
        return [f"out{i}" for i in range(len(self._fetch) or 1)]

    def get_output_handle(self, name: str) -> _IOTensor:
        return _IOTensor(self, name, False)

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        if inputs is None:
            names = self.get_input_names()
            missing = [k for k in names if k not in self._feed]
            if missing:
                raise ValueError(
                    f"inputs not fed: {missing}; call copy_from_cpu on "
                    "every input handle before run()")
            inputs = [self._feed[k] for k in names]
        outs = self._loaded(*[np.asarray(a) for a in inputs])
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        self._fetch = {f"out{i}": np.asarray(o.numpy())
                       for i, o in enumerate(outs)}
        return [self._fetch[f"out{i}"] for i in range(len(outs))]


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


class PredictorPool:
    """Fixed pool of Predictors for multi-threaded callers
    (paddle_infer::services::PredictorPool parity).

    Hand-out is thread-safe: ``acquire()`` pops the oldest free slot
    (FIFO) under a condition variable and ``release()`` returns it —
    the free-list bookkeeping is the shared state; Predictor.run
    itself is per-instance. ``retrieve(idx)`` keeps the reference's
    direct-index contract."""

    def __init__(self, config: Config, size: int = 1):
        self._preds = [Predictor(config) for _ in range(size)]
        self._mu = threading.Lock()
        self._free = list(range(size))
        self._cv = threading.Condition(self._mu)

    def retrieve(self, idx: int) -> Predictor:
        return self._preds[idx]

    def acquire(self, timeout: Optional[float] = None) -> Predictor:
        """Check out a free Predictor (blocks until one is released;
        raises TimeoutError past ``timeout`` seconds)."""
        with self._cv:
            if not self._cv.wait_for(lambda: bool(self._free),
                                     timeout=timeout):
                raise TimeoutError("no free Predictor in pool")
            idx = self._free.pop(0)
            pred = self._preds[idx]
            pred._pool_idx = idx
            return pred

    def release(self, pred: Predictor) -> None:
        with self._cv:
            idx = getattr(pred, "_pool_idx", None)
            if idx is None or self._preds[idx] is not pred:
                raise ValueError("predictor does not belong to this pool")
            if idx in self._free:
                raise ValueError(f"double release of pool slot {idx}")
            self._free.append(idx)
            self._cv.notify()


def get_version() -> str:
    from .version import full_version
    return full_version
