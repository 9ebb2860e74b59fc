"""Building the system under test from a configuration file, and
handing it the benchmark's seeded weights."""

from __future__ import annotations


from common import resolve
from weights import make_weights


def build_model(cfg: dict, overrides: dict):
    """The program's model of configuration ``cfg``: its config class
    fed the published keys under the program's own argument names, then
    the cell's ``overrides`` (execution choices, never sizes)."""
    prog = cfg["program"]
    kwargs = {arg: cfg[key] for arg, key in prog["config_kwargs"].items()}
    kwargs.update(prog.get("config_constants", {}))
    kwargs.update(overrides)
    model_cfg = resolve(prog["config_class"])(**kwargs)
    return resolve(prog["model_class"])(model_cfg), model_cfg


def leaf_of_param(cfg: dict, layout: str) -> dict:
    """{program parameter name: (reference leaf, layer index or None)}."""
    layers = cfg[cfg["program"]["layers_key"]]
    out = {}
    for leaf, template in cfg["program"]["layouts"][layout].items():
        if "{i}" in template:
            for i in range(layers):
                out[template.replace("{i}", str(i))] = (leaf, i)
        else:
            out[template] = (leaf, None)
    return out


def set_weights(model, cfg: dict, layout: str, reference, seed: int) -> None:
    """Every parameter of ``model`` set from the seed: the reference's
    leaves, drawn by ``weights.make_weights``, placed as the parameter
    is placed and cast to its type."""
    import jax
    import paddle2_tpu as paddle
    leaves = make_weights(reference.leaf_specs(cfg), seed)
    where = leaf_of_param(cfg, layout)
    for name, p in model.named_parameters():
        if name not in where:
            raise KeyError(f"parameter {name!r} has no leaf in layout "
                           f"{layout!r} of {cfg['name']}")
        leaf, i = where[name]
        arr = leaves[leaf] if i is None else leaves[leaf][i]
        arr = arr.astype(p._data.dtype)
        if len(p._data.sharding.device_set) > 1:
            # a parameter the program spread over the mesh keeps its
            # placement; the others stay uncommitted, as it made them
            arr = jax.device_put(arr, p._data.sharding)
        p.set_value(paddle.Tensor(arr))
    del leaves


def apply_runtime_env(workload: dict) -> None:
    """Runtime settings that must be in the environment before the
    backend starts, named in the workload file as data."""
    for dotted in workload.get("runtime_env_calls", []):
        resolve(dotted["call"])(**dotted.get("kwargs", {}))


