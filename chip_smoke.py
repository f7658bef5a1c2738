#!/usr/bin/env python
"""Chip smoke test: drive the CIM serving path once on a TPU.

    python chip_smoke.py [--seed N]               # one chip: phases A, B
    python chip_smoke.py --four-chips [--seed N]  # four chips: phase C only

* **A, kernel.**  One seeded matrix at phi3-mini's ``w_gate`` shape
  (3072x8192) is deployed onto 64x64 crossbars (8-bit slices, MDM
  mapping).  ``cim_mvm`` runs under ``impl="auto"``, which must resolve
  to the Pallas kernel, and under ``impl="xla"``; both are compared with
  the materialised Eq-17 reference ``cim_mvm_ref``.
* **B, serve.**  phi3-mini-3.8b at its published widths, depth cut to 2
  layers, with CIM on (MDM, ideal devices) and seeded weights, serves 8
  greedy requests through ``ContinuousEngine``.  The prefill logits of
  one request are compared with a float32 forward pass on Eq-17
  distorted dense weights (``noisy_weights``).
* **C, four chips** (``--four-chips`` only).  The sharded crossbar
  solver over a 4-device tile mesh against the single-chip batched
  solver on the same 512 seeded 64x64 tiles.

The script is one process and starts none.  It exits non-zero, and
prints no result line, when JAX finds no TPU.  Lines starting with
``smoke`` are one-shot readings of this run, not benchmark numbers.
The last line is one JSON object: ``{"ok": ..., "device": {"platform":
..., "kind": ..., "count": ...}}``; ``ok`` is true only if every phase
passed.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# Phase A: norm-scaled error ||y - y_ref|| / ||y_ref|| of the kernel and
# of the XLA path against the reference.  All three evaluate Eq 17 in
# f32 and contract at f32 precision ("highest"), so they differ by f32
# rounding in different summation orders: about sqrt(3072) * 2^-24
# ~ 3e-6.  1e-4 leaves 30x headroom for that, and is 20x below a single
# bf16 rounding (2^-9 ~ 2e-3), so a contraction that silently ran in
# bf16 fails.  The Eq-17 distortion must be at least 10x this bound for
# the comparison to say anything about it.
KERNEL_TOL = 1e-4
# Phase B: norm-scaled error of the served model's prefill logits
# against the f32 reference.  The served model keeps activations, the
# residual stream, attention and the digital embedding/head matmuls in
# bfloat16 (unit roundoff 2^-9 ~ 2e-3).  Each layer rounds about ten
# times, and random-weight logits amplify nothing, so the expected error
# over 2 layers is ~1e-2; 3e-2 allows that and no more.
LOGITS_TOL = 3e-2
# Phase C: per-tile relative difference of nf_total between the sharded
# and the single-chip solver.  nf_total is a cancellation-amplified
# |sum di| over CG currents that agree to ~1e-5, so 1e-4 is the
# tightest bound the two solve paths support.
SOLVER_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class KernelSize:
    in_dim: int
    out_dim: int
    rows: int
    cols: int
    n_bits: int
    batch_rows: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class ServeSize:
    preset: str          # "full" (published widths) or "smoke"
    n_layers: int
    capacity: int
    max_seq: int
    max_prompt: int
    min_prompt: int
    n_requests: int
    max_tokens: int


@dataclasses.dataclass(frozen=True)
class SolverSize:
    n_tiles: int
    rows: int
    cols: int
    sparsity: float
    n_devices: int


FULL_KERNEL = KernelSize(3072, 8192, 64, 64, 8, (8, 256))
FULL_SERVE = ServeSize("full", 2, 8, 512, 256, 32, 8, 32)
FULL_SOLVER = SolverSize(512, 64, 64, 0.8, 4)
SMOKE_KERNEL = KernelSize(128, 48, 32, 32, 8, (8,))
SMOKE_SERVE = ServeSize("smoke", 2, 8, 64, 32, 4, 8, 8)
SMOKE_SOLVER = SolverSize(16, 16, 16, 0.8, 4)


def _rel(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def report(phase: str, **fields) -> None:
    """One labelled line of smoke output (not a benchmark number)."""
    print("smoke " + json.dumps({"phase": phase, **fields}), flush=True)


@contextlib.contextmanager
def compile_clock():
    """Sum of XLA backend compile seconds inside the block."""
    import jax

    event = "/jax/core/compile/backend_compile_duration"
    total = {"seconds": 0.0, "compiles": 0}

    def listen(name, seconds, **_):
        if name == event:
            total["seconds"] += seconds
            total["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def peak_bytes() -> int | str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# --------------------------------------------------------------- phase A


def phase_kernel(size: KernelSize, seed: int,
                 kernel_impl: str = "auto") -> dict:
    """Kernel vs XLA vs the Eq-17 reference at one matrix shape.

    ``kernel_impl`` is "auto" on the chip, where it must serve through
    the Pallas kernel; CPU tests pass "interpret".
    """
    import jax
    import jax.numpy as jnp

    from repro.core.noise import PAPER_ETA
    from repro.core.tiling import CrossbarSpec
    from repro.kernels.cim_mvm import cim_mvm, deploy, served_impl
    from repro.kernels.cim_mvm.ref import cim_mvm_ref

    spec = CrossbarSpec(size.rows, size.cols, size.n_bits)
    kw, kx = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(kw, (size.in_dim, size.out_dim)) * 0.02
    dep, plan = deploy(w, spec, "mdm", eta=PAPER_ETA)
    # A per-weight gain is what injected nonidealities add; with gain 1
    # it must give the ideal answer through the XLA path.
    nonideal = dataclasses.replace(
        dep, gain=jnp.ones(dep.codes.shape, jnp.float32))
    out = {"shape": [size.in_dim, size.out_dim],
           "served_ideal": served_impl(dep, kernel_impl),
           "served_nonideal": served_impl(nonideal),
           "tolerance": KERNEL_TOL, "batches": []}
    ok = True
    codes = dep.codes.astype(jnp.int32)
    for i, m in enumerate(size.batch_rows):
        x = jax.random.normal(jax.random.fold_in(kx, i), (m, size.in_dim))
        x_pad = jnp.pad(x, ((0, 0), (0, codes.shape[0] - size.in_dim)))
        with jax.default_matmul_precision("highest"):
            y_kernel = cim_mvm(x, dep, impl=kernel_impl)
            y_xla = cim_mvm(x, dep, impl="xla")
            y_nonideal = cim_mvm(x, nonideal)
            y_ref = cim_mvm_ref(x_pad, codes, plan, spec,
                                dep.eta)[:, :size.out_dim]
            y_clean = cim_mvm_ref(x_pad, codes, plan, spec,
                                  0.0)[:, :size.out_dim]
        row = {"m": m,
               "err_kernel": _rel(y_kernel, y_ref),
               "err_xla": _rel(y_xla, y_ref),
               "err_nonideal_xla": _rel(y_nonideal, y_ref),
               "eq17_distortion": _rel(y_clean, y_ref)}
        row["ok"] = (max(row["err_kernel"], row["err_xla"],
                         row["err_nonideal_xla"]) <= KERNEL_TOL
                     and row["eq17_distortion"] >= 10 * KERNEL_TOL)
        ok &= row["ok"]
        out["batches"].append(row)
    out["ok"] = bool(ok)
    return out


# --------------------------------------------------------------- phase B


def serve_config(size: ServeSize):
    from repro.configs.base import CimConfig
    from repro.configs.phi3_mini_38b import CONFIG, SMOKE

    base = CONFIG if size.preset == "full" else SMOKE
    return base.replace(n_layers=size.n_layers,
                        cim=CimConfig(enabled=True, mode="mdm"))


def distorted_reference_params(params, cfg):
    """float32 params whose deployed matrices carry Eq 17 (noisy_weights)."""
    import jax
    import jax.numpy as jnp

    from repro.core.noise import noisy_weights
    from repro.deploy.engine import DEPLOYABLE, _as_matrix, spec_from_config

    spec = spec_from_config(cfg)
    ref = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    for i, bt in enumerate(cfg.block_pattern):
        slot = ref[f"slot{i}_{bt}"]
        for pname in DEPLOYABLE:
            if pname not in slot:
                continue
            layers = []
            for w in slot[pname]:
                wd, _ = noisy_weights(_as_matrix(pname, w), spec,
                                      cfg.cim.mode, cfg.cim.eta)
                layers.append(wd.reshape(w.shape))
            slot[pname] = jnp.stack(layers)
    return ref


def phase_serve(size: ServeSize, seed: int, plan_cache=None,
                clock: dict | None = None) -> dict:
    """ContinuousEngine over the CIM path, plus a logits reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import telemetry as tm
    from repro.configs.base import CimConfig
    from repro.distributed.sharding import ShardingCtx
    from repro.kernels.cim_mvm import served_impl
    from repro.models.model import apply_model, init_params
    from repro.serve.continuous import ContinuousEngine

    cfg = serve_config(size)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    eng = ContinuousEngine(cfg, params, capacity=size.capacity,
                           max_seq=size.max_seq, max_prompt=size.max_prompt,
                           plan_cache=plan_cache)
    bank = eng.banks[eng.serving_epoch]
    dep = bank.cim[f"slot0_{cfg.block_pattern[0]}"]["ffn_w_gate"]

    rng = np.random.default_rng(seed)
    lengths = rng.integers(size.min_prompt, size.max_prompt + 1,
                           size.n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)) for n in lengths]
    rids = [eng.submit(p, max_tokens=size.max_tokens) for p in prompts]
    t0 = tm.monotonic()
    results = eng.run()
    serve_s = tm.monotonic() - t0
    n_tokens = [len(results.get(r, [])) for r in rids]

    ctx = ShardingCtx()
    forward = jax.jit(lambda p, c, cim, t: apply_model(
        p, c, ctx, tokens=t, cim=cim)[0], static_argnums=1)
    toks = jnp.asarray(prompts[0])[None]
    # Real vocabulary only: the padded columns are pinned at -1e9 and
    # would swamp any norm.
    vocab = slice(0, cfg.vocab_size)
    logits = forward(bank.params, cfg, bank.cim, toks)[..., vocab]
    ref_cfg = cfg.replace(dtype="float32", cim=CimConfig())
    ref_params = distorted_reference_params(params, cfg)
    with jax.default_matmul_precision("highest"):
        ref = forward(ref_params, ref_cfg, None, toks)[..., vocab]
        clean = forward(jax.tree.map(lambda a: a.astype(jnp.float32),
                                     params), ref_cfg, None,
                        toks)[..., vocab]
    out = {
        "config": f"{cfg.name} n_layers={cfg.n_layers} (depth cut from "
                  f"32; published widths)" if size.preset == "full"
                  else f"{cfg.name} smoke preset",
        "served_ideal": served_impl(dep),
        "deploy_seconds": eng.deploy_report["deploy_seconds"],
        "compile_seconds": (clock["seconds"] if clock is not None
                            else "not measured"),
        "serve_seconds_incl_compile": serve_s,
        "prompt_lengths": [int(n) for n in lengths],
        "tokens_generated": int(sum(n_tokens)),
        "traces": dict(eng.traces),
        "logits_err": _rel(logits, ref),
        "logits_eq17_distortion": _rel(clean, ref),
        "logits_tolerance": LOGITS_TOL,
        "peak_bytes_in_use": peak_bytes(),
    }
    out["ok"] = bool(
        all(n == size.max_tokens for n in n_tokens)
        and eng.traces == {"prefill": 1, "decode": 1}
        and out["logits_err"] <= LOGITS_TOL)
    return out


# --------------------------------------------------------------- phase C


def solver_precision() -> str:
    """"f64" where the device computes real float64, else "f32"."""
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import enable_x64

    with enable_x64():
        one = jnp.ones((), jnp.float64)
        tiny = jnp.asarray(2.0 ** -40, jnp.float64)
        got = np.asarray(one + tiny)
    return "f64" if got.dtype == np.float64 and got != 1.0 else "f32"


def phase_solver(size: SolverSize, seed: int) -> dict:
    """Sharded solver over a tile mesh vs the single-device solver."""
    import jax
    import numpy as np

    from repro.compat import has_batched_tridiagonal_solve
    from repro.core.tiling import CrossbarSpec
    from repro.crossbar.batched import measured_nf_batched
    from repro.distributed.solver_shard import (
        measured_nf_sharded,
        tile_sharding_ctx,
    )

    spec = CrossbarSpec(size.rows, size.cols, 8)
    masks = (jax.random.uniform(jax.random.PRNGKey(seed),
                                (size.n_tiles, size.rows, size.cols))
             < 1.0 - size.sparsity).astype(np.float32)
    precision = solver_precision()
    ctx = tile_sharding_ctx(size.n_devices)
    one = measured_nf_batched(masks, spec, precision=precision)
    sharded = measured_nf_sharded(masks, spec, precision=precision,
                                  ctx=ctx)
    a = np.asarray(sharded.nf_total, np.float64)
    b = np.asarray(one.nf_total, np.float64)
    rel = np.abs(a - b) / np.maximum(np.abs(b), np.finfo(np.float64).tiny)
    out = {"tiles": size.n_tiles, "tile": [size.rows, size.cols],
           "sparsity": size.sparsity,
           "mesh_devices": int(ctx.mesh.devices.size),
           "precision": precision,
           "preconditioner": ("line (batched tridiagonal_solve)"
                              if has_batched_tridiagonal_solve()
                              else "jacobi (no batched tridiagonal_solve)"),
           "nf_total_mean": float(b.mean()),
           "nf_total_max_rel_diff": float(rel.max()),
           "unconverged_sharded": int(sharded.unconverged),
           "tolerance": SOLVER_TOL}
    out["ok"] = bool(rel.max() <= SOLVER_TOL and out["unconverged_sharded"]
                     == 0 and np.all(np.isfinite(a)))
    return out


# ------------------------------------------------------------------ main


def _run(name: str, fn, *args, **kw) -> bool:
    """Run one phase; a phase that raises fails and the next still runs."""
    try:
        out = fn(*args, **kw)
    except Exception:  # the smoke reports every phase's outcome
        traceback.print_exc()
        report(name, ok=False, error="raised; traceback on stderr")
        return False
    report(name, **out)
    return out["ok"]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded solver phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: the repro package is not next to this script "
              f"(looked in {src})", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.cachedirs import setup_compile_cache

    setup_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devices}); "
              "this script runs on the chip only", file=sys.stderr)
        return 1
    need = FULL_SOLVER.n_devices if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    if args.four_chips:
        ok = _run("C_sharded_solver", phase_solver, FULL_SOLVER, args.seed)
    else:
        ok = _run("A_kernel", phase_kernel, FULL_KERNEL, args.seed)
        with compile_clock() as clock:
            ok &= _run("B_serve", phase_serve, FULL_SERVE, args.seed + 1,
                       clock=clock)
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
