"""Benchmark: MDM planning overhead (the paper's "lightweight" claim).

Times plan_layer (bit-slice + score + sort + NF bookkeeping) and the
Pallas scoring kernel on layer-sized matrices, plus the fused
whole-model planner (``repro.deploy``) on the same workload expressed
as a multi-matrix population; MDM is a one-off deployment-time
transformation, so these must be trivially small next to
training/serving costs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.mdm import plan_layer
from repro.core.tiling import CrossbarSpec
from repro.deploy import plan_matrices
from repro.kernels.manhattan_score import manhattan_score
from repro.kernels.runtime import default_interpret


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run(verbose: bool = True) -> dict:
    spec = CrossbarSpec(rows=64, cols=64, n_bits=8)
    key = jax.random.PRNGKey(0)
    out = {}
    layers = {}
    for (i, n) in [(1024, 1024), (4096, 4096)]:
        w = jax.random.normal(jax.random.fold_in(key, i), (i, n)) * 0.02
        layers[f"{i}x{n}"] = w
        dt = _time(lambda w: plan_layer(w, spec, "mdm"), w)
        ti, tn = spec.grid(i, n)
        out[f"plan_{i}x{n}"] = {"seconds": dt, "tiles": ti * tn,
                                "us_per_tile": dt / (ti * tn) * 1e6}
        if verbose:
            print(f"  plan_layer {i}x{n}: {dt*1e3:.1f} ms "
                  f"({ti*tn} tiles, {dt/(ti*tn)*1e6:.1f} us/tile)")

    # Fused whole-model planner on the same matrices as one population.
    def fused(mats):
        plans, _ = plan_matrices(mats, spec, "mdm")
        return jax.block_until_ready(
            jnp.stack([p.nf_after.sum() for p in plans.values()]))

    dt = _time(fused, layers)
    tiles = sum(v["tiles"] for k, v in out.items() if k.startswith("plan_"))
    out["plan_model_fused"] = {"seconds": dt, "tiles": tiles,
                               "us_per_tile": dt / tiles * 1e6}
    if verbose:
        print(f"  fused whole-model planner ({len(layers)} matrices, "
              f"{tiles} tiles): {dt*1e3:.1f} ms "
              f"({dt/tiles*1e6:.1f} us/tile)")

    masks = (jax.random.uniform(jax.random.fold_in(key, 0),
                                (256, 64, 64)) < 0.2).astype(jnp.uint8)
    dt = _time(lambda m: manhattan_score(m, nf_unit=spec.nf_unit), masks)
    interpret = default_interpret()
    out["score_kernel_256tiles"] = {"seconds": dt, "interpret": interpret}
    if verbose:
        label = "interpret" if interpret else "compiled"
        print(f"  manhattan_score kernel (256 tiles, {label}): "
              f"{dt*1e3:.1f} ms")
    return out


if __name__ == "__main__":
    run()
