"""Ideal devices: no programming variation, read noise, drift or
faults.  Every deployed matrix keeps its Eq-17 parasitic distortion
alone, so the program serves it by the Pallas ``cim_mvm`` kernel."""


def engine_kwargs(spec: dict) -> dict:
    """Keyword arguments of ``ContinuousEngine`` for this device model."""
    if set(spec) != {"model"}:
        raise ValueError(f"ideal devices take no settings: {spec}")
    return {}
