"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program passes through here:
the model configuration it is given, the serving engine it drives
(``ContinuousEngine``: deploy, ``submit``, ``step``), and the report
and kernel path that the engine states.

A configuration file is mapped key by key.  A key that the program is
not given, that only describes the configuration, or whose one served
value is fixed, is named below; any other key is refused, so that no
setting of a configuration file is dropped in silence.  The device
model is a builder of its own, ``chipbench/devices/<model>.py``.
"""
from __future__ import annotations

# Configuration key -> the ModelConfig field it sets.
FIELDS = {
    "family": "family", "block_pattern": "block_pattern",
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "sliding_window": "sliding_window", "mlp_type": "mlp_type",
    "torch_dtype": "dtype",
}
# Keys the program has no option for: the one value it serves.
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False}
# Keys that describe the configuration, or the benchmark's use of it.
DESCRIPTIVE = {"name", "source", "paper", "model_type", "architecture",
               "reference", "reduced", "assumed", "deployment", "served",
               "max_position_embeddings", "limits"}
# ``cim`` key -> the CimConfig field it sets; the rest describe.
CIM_FIELDS = {"mapping": "mode", "eta": "eta", "rows": "rows",
              "cols": "cols", "n_bits": "n_bits", "r": "r",
              "r_on": "r_on", "r_off": "r_off"}
CIM_DESCRIPTIVE = {"deployed", "digital"}


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import CimConfig, ModelConfig

    unknown = set(cfg) - set(FIELDS) - set(FIXED) - DESCRIPTIVE \
        - {"cim", "devices"}
    unknown |= {f"cim.{k}" for k in set(cfg["cim"]) - set(CIM_FIELDS)
                - CIM_DESCRIPTIVE}
    if unknown:
        raise ValueError(f"{cfg['name']}: configuration keys the program "
                         f"is not given: {sorted(unknown)}")
    for key, value in FIXED.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r}; the "
                             f"program serves {value!r} only")
    kw = {field: cfg[key] for key, field in FIELDS.items() if key in cfg}
    kw["block_pattern"] = tuple(kw["block_pattern"])
    kw["head_dim"] = kw.get("head_dim") or 0
    kw["sliding_window"] = int(kw.get("sliding_window") or 0)
    cim = {field: cfg["cim"][key] for key, field in CIM_FIELDS.items()
           if key in cfg["cim"]}
    return ModelConfig(name=cfg["name"], **kw,
                       cim=CimConfig(enabled=True, **cim))


def make_engine(cfg: dict, mix: dict, params, plan_root: str):
    """Deploy ``params`` and return the serving engine for one cell."""
    from chipbench.harness import load_module
    from repro.deploy import PlanCache
    from repro.serve.continuous import ContinuousEngine

    devices = load_module("devices", cfg["devices"]["model"])
    return ContinuousEngine(
        model_config(cfg), params, capacity=mix["capacity"],
        max_seq=mix["max_seq"], max_prompt=mix["max_prompt"],
        plan_cache=PlanCache(plan_root),
        **devices.engine_kwargs(cfg["devices"]))


def served_paths(eng) -> list[str]:
    """The ``cim_mvm`` path each deployed matrix is served by."""
    from repro.kernels.cim_mvm import served_impl

    bank = eng.banks[eng.serving_epoch]
    return sorted({served_impl(dep) for slot in bank.cim.values()
                   for dep in slot.values()})
