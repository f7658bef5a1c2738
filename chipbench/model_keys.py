"""The model keys of a configuration file, mapped to the program's
``ModelConfig``, for a program that has no loader of its own.

``chipbench.program.model_config`` strips the benchmark's own keys and
hands the rest to the program's ``repro.configs.from_file``
``model_config_from_file`` where the program has one; until then, to
``model_config_from_file`` here.  It imports nothing of the benchmark,
and no ``jax`` at import time, so that the first change that adds a
configuration makes the program's module from this one as it stands and
adds its keys there, not here.  From then on this module serves only a
program without that loader, such as the parent side of that change's
comparison, and a later change to the benchmark deletes it together
with the fallback in ``chipbench.program.loader``.

A key that the program is not given, that only describes the model, or
whose one served value is fixed, is named below; any other key is
refused, so that no setting of a configuration file is dropped in
silence.
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
# Keys that describe the model.
DESCRIPTIVE = {"name", "model_type", "architecture",
               "max_position_embeddings"}
# ``cim`` key -> the CimConfig field it sets; the rest describe.
CIM_FIELDS = {"mapping": "mode", "eta": "eta", "rows": "rows",
              "cols": "cols", "n_bits": "n_bits", "r": "r",
              "r_on": "r_on", "r_off": "r_off"}
CIM_DESCRIPTIVE = {"deployed", "digital"}


def model_config_from_file(cfg: dict):
    """The ModelConfig for a configuration file's model keys."""
    from repro.configs.base import CimConfig, ModelConfig

    unknown = set(cfg) - set(FIELDS) - set(FIXED) - DESCRIPTIVE - {"cim"}
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
