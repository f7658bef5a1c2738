"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program passes through here:
the model configuration it is given, the serving engine it drives
(``ContinuousEngine``: deploy, ``submit``, ``step``), and the report
and kernel path that the engine states.

A configuration file holds the benchmark's own keys (``BENCH_KEYS``:
where the model comes from, how it was cut, its device model, its
reference and limits) and the model's keys.  The model's keys are
mapped to ``ModelConfig`` by the program's loader,
``repro.configs.from_file.model_config_from_file``, which refuses any
key it does not map; where the program has no such module, by the same
function in ``chipbench/model_keys.py``.  The device model comes from
a module of its own, ``chipbench/devices/<model>.py``.
"""
from __future__ import annotations

import importlib.util

# Keys of a configuration file that the benchmark reads, never the
# program.
BENCH_KEYS = ("source", "paper", "reference", "reduced", "assumed",
              "deployment", "served", "limits", "devices")


def loader():
    """``model_config_from_file``: the program's own where it has one,
    else ``chipbench.model_keys``'s, kept for a program without it."""
    if importlib.util.find_spec("repro.configs.from_file") is not None:
        from repro.configs.from_file import model_config_from_file
    else:
        from chipbench.model_keys import model_config_from_file
    return model_config_from_file


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: its model
    keys, passed to ``loader()``."""
    return loader()({k: v for k, v in cfg.items() if k not in BENCH_KEYS})


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
