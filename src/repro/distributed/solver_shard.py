"""Device-sharded crossbar solver: the layer-scale NF sweep engine.

A real DNN layer shards into thousands of crossbar tiles (a ResNet
conv layer at 64x64 tiles is ~1k-10k of them), and the solver state is
embarrassingly parallel over the tile axis — so the fused batched PCG
of :mod:`repro.crossbar.batched` scales out by simply splitting the
tile batch across a device mesh:

* the tile batch is laid out over a 1-D ``"tiles"`` mesh (all local
  devices by default) or any :class:`repro.distributed.sharding
  .ShardingCtx` mesh whose rules resolve the logical ``"tiles"`` dim;
* each shard runs the *whole* fused CG loop (:func:`repro.crossbar.
  batched._solve_core`) on its local tile slice under
  :func:`repro.compat.shard_map` — there are **no collectives inside
  the iteration loop**, so every shard early-exits the moment its own
  tiles converge instead of spinning until the globally worst tile is
  done (per-shard early exit);
* the only cross-device communication is the **global convergence
  check after the loop**: one ``psum`` counts still-unconverged tiles
  across shards and one ``pmax`` reports the worst-shard iteration
  count, both replicated so the host reads them without a gather;
* the preconditioner kernel is selectable per call (``chain_impl``):
  the default ``"lax"`` scan is work-optimal and lets the concurrent
  shard programs hide its sequential-step latency across the host's
  cores; ``"assoc"`` (Thomas factorisation applied via log-depth
  associative scans, no backend-specific lowering needed) wins when
  shards run with idle compute to spare — isolated solves, or
  accelerators without a batched ``tridiagonal_solve`` lowering;
* batches that don't divide the shard count are padded with zero-drive
  tiles (``b = 0`` makes them converge at iteration 0) and unpadded on
  the way out;
* the mesh is an ordinary ``jax.sharding.Mesh``, so the same code is
  mesh-ready for multi-host: on a multi-process runtime the ``"tiles"``
  axis simply spans all processes' local devices.

Precision composes orthogonally: pass any
:class:`repro.crossbar.batched.SolverPrecision` (e.g. ``MIXED`` for
f32 CG + f64 polish) and each shard runs that policy locally.
Throughput rows for sharded/mixed configurations are recorded by
``benchmarks/solver_throughput.py``.
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import enable_x64, shard_map
from repro.core.tiling import CrossbarSpec
from repro.crossbar.batched import (
    SolverPrecision,
    SolverReport,
    _escalate_failed,
    _ref_subset,
    _solve_core,
    _solve_core_g,
    record_solver_report,
    resolve_precision,
    solve_conductances_batched,
    tile_converged,
)
from repro.distributed.sharding import ShardingCtx, logical_spec

TILE_AXIS = "tiles"


class ShardedSolveResult(NamedTuple):
    """Per-tile results plus the post-loop global convergence check.

    The first six fields mirror
    :class:`repro.crossbar.batched.BatchedSolveResult` (consumers can
    treat the two interchangeably); ``iterations`` is the worst shard's
    count (pmax) and ``unconverged`` the psum-reduced number of tiles
    that hit ``maxiter`` without passing ``tol`` — 0 means the whole
    layer population converged.
    """

    currents: jax.Array
    ideal: jax.Array
    nf_cols: jax.Array
    nf_total: jax.Array
    residual: jax.Array
    iterations: jax.Array
    unconverged: jax.Array


def tile_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the local devices with the canonical tile axis."""
    devs = jax.local_devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (TILE_AXIS,))


def tile_sharding_ctx(n_devices: int | None = None) -> ShardingCtx:
    """ShardingCtx whose mesh shards the logical "tiles" dim locally."""
    return ShardingCtx(mesh=tile_mesh(n_devices))


def _tile_axes(mesh: Mesh, rules) -> tuple[str, ...]:
    """Mesh axes the logical "tiles" dim shards over (rule-resolved).

    The dummy size passed to :func:`logical_spec` is the mesh's total
    device count, which every candidate divides — actual divisibility
    is handled by padding in :func:`measured_nf_sharded`.
    """
    total = 1
    for s in mesh.shape.values():
        total *= s
    spec = logical_spec((total,), (TILE_AXIS,), mesh, rules)
    if not spec:
        # Rules resolved "tiles" to replicated (e.g. a model-only mesh):
        # run unsharded on one device rather than failing.
        return ()
    axes = spec[0]
    return (axes,) if isinstance(axes, str) else tuple(axes)


@lru_cache(maxsize=None)
def _sharded_solver(mesh: Mesh, axes: tuple[str, ...], maxiter: int,
                    tol: float, precision: SolverPrecision,
                    chain_impl: str):
    """Build + cache the jitted shard_mapped solve for one config.

    Cached on (mesh, axes, maxiter, tol, precision, chain_impl) so
    repeated sweep calls reuse the compiled executable instead of
    re-tracing.
    """

    def local(active, v_in, spec_arr):
        # Each shard solves its slice with local early exit; the loop
        # body contains no collectives by construction.
        res = _solve_core(active, v_in, spec_arr, maxiter, tol, precision,
                          chain_impl)
        # Global convergence check — the solve's only communication.
        # NaN/Inf-aware (tile_converged): ``residual > tol`` is False
        # for NaN, which would count a diverged tile as converged.
        unconverged = jax.lax.psum(
            jnp.sum((~tile_converged(res, tol)).astype(jnp.int32)), axes)
        # int32: XLA:TPU lowers only the sum all-reduce for 64-bit ints,
        # and the x64 scope makes the loop counter int64.
        iters = jax.lax.pmax(res.iterations.astype(jnp.int32), axes)
        return ShardedSolveResult(res.currents, res.ideal, res.nf_cols,
                                  res.nf_total, res.residual, iters,
                                  unconverged)

    tiled = P(axes)
    out = ShardedSolveResult(tiled, tiled, tiled, tiled, tiled, P(), P())
    # check_vma=False: per-shard trip counts are data-dependent by
    # design (that is the early-exit win), which the replication checker
    # cannot express; the replicated outputs are produced by explicit
    # collectives above.
    fn = shard_map(local, mesh=mesh,
                   in_specs=(tiled, tiled, P()), out_specs=out,
                   check_vma=False)
    return jax.jit(fn)


@lru_cache(maxsize=None)
def _sharded_solver_g(mesh: Mesh, axes: tuple[str, ...], maxiter: int,
                      tol: float, precision: SolverPrecision,
                      chain_impl: str):
    """Conductance-field variant of :func:`_sharded_solver`.

    Same shard layout and post-loop global check, but the per-shard body
    is :func:`repro.crossbar.batched._solve_core_g` over perturbed /
    reference conductance pairs — the scale-out tier of the Monte-Carlo
    nonideality engine (:mod:`repro.nonideal.montecarlo`), whose sample
    axis is folded into the sharded tile axis.
    """

    def local(g, g_ref, v_in, spec_arr):
        res = _solve_core_g(g, g_ref, v_in, spec_arr, maxiter, tol,
                            precision, chain_impl)
        unconverged = jax.lax.psum(
            jnp.sum((~tile_converged(res, tol)).astype(jnp.int32)), axes)
        iters = jax.lax.pmax(res.iterations.astype(jnp.int32), axes)  # see above
        return ShardedSolveResult(res.currents, res.ideal, res.nf_cols,
                                  res.nf_total, res.residual, iters,
                                  unconverged)

    tiled = P(axes)
    out = ShardedSolveResult(tiled, tiled, tiled, tiled, tiled, P(), P())
    fn = shard_map(local, mesh=mesh,
                   in_specs=(tiled, tiled, tiled, P()), out_specs=out,
                   check_vma=False)
    return jax.jit(fn)


def solve_crossbar_sharded(active: jax.Array, v_in: jax.Array,
                           spec_arr: jax.Array, mesh: Mesh,
                           axes: tuple[str, ...], maxiter: int = 4000,
                           tol: float = 1e-12,
                           precision: SolverPrecision | None = None,
                           chain_impl: str = "lax"
                           ) -> ShardedSolveResult:
    """Shard a (T, J, K) tile batch over ``axes`` of ``mesh`` and solve.

    ``T`` must already be a multiple of the sharded device count and
    ``v_in`` already broadcast to (T, J) — :func:`measured_nf_sharded`
    is the padding/broadcasting front door.  ``chain_impl`` picks the
    preconditioner kernel (see
    :func:`repro.crossbar.batched._line_preconditioner`): "lax" is
    work-optimal when the shards saturate the host, "assoc" is the
    portable log-depth kernel for backends without a batched
    ``tridiagonal_solve`` lowering.
    """
    precision = resolve_precision(precision)
    return _sharded_solver(mesh, tuple(axes), maxiter, float(tol),
                           precision, chain_impl)(active, v_in, spec_arr)


def measured_nf_sharded(active: jax.Array, spec: CrossbarSpec,
                        v_in: jax.Array | None = None,
                        maxiter: int = 4000,
                        precision: SolverPrecision | str | None = None,
                        ctx: ShardingCtx | None = None,
                        tol: float = 1e-12,
                        chain_impl: str = "lax") -> ShardedSolveResult:
    """Circuit-measured NF of a layer-scale tile population, sharded.

    Drop-in scale-out of :func:`repro.crossbar.batched
    .measured_nf_batched`: ``active`` is (..., J, K) with arbitrary
    leading batch dims; the result carries the same leading dims plus
    the global convergence fields.  ``ctx`` supplies the mesh (default:
    a fresh 1-D mesh over all local devices); the logical "tiles" dim
    is resolved through the ctx's sharding rules, so the same call
    works on a dedicated tile mesh or on the data axis of a training
    mesh.  Tile counts that don't divide the shard count are padded
    with zero-drive tiles (converged at iteration 0) and unpadded.
    """
    precision = resolve_precision(precision)
    if ctx is None or ctx.mesh is None:
        ctx = tile_sharding_ctx()
    mesh = ctx.mesh
    axes = _tile_axes(mesh, ctx.rules)
    if not axes:
        # Rules replicate "tiles" on this mesh: degrade to the fused
        # single-device engine, synthesising the global-check fields.
        from repro.crossbar.batched import measured_nf_batched
        res = measured_nf_batched(active, spec, v_in, maxiter, precision)
        return ShardedSolveResult(
            *res[:5], res.iterations,
            jnp.sum((~tile_converged(res, tol)).astype(jnp.int32)))
    n_shards = 1
    for a in axes:
        n_shards *= dict(mesh.shape)[a]

    with enable_x64():
        spec_arr = jnp.array([spec.r, spec.r_on, spec.r_off], jnp.float64)
        if v_in is None:
            v_in = jnp.full((active.shape[-2],), spec.v_read, jnp.float64)
        batch_shape = active.shape[:-2]
        flat = active.reshape((-1,) + active.shape[-2:])
        T, J = flat.shape[0], flat.shape[1]
        v = jnp.broadcast_to(
            v_in.astype(jnp.float64),
            (T, J) if v_in.ndim == 1 else v_in.shape
        ).reshape(T, J)

        pad = (-T) % n_shards
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,) + flat.shape[1:], flat.dtype)])
            v = jnp.concatenate([v, jnp.zeros((pad, J), v.dtype)])

        res = solve_crossbar_sharded(flat, v, spec_arr, mesh, axes,
                                     maxiter, tol, precision, chain_impl)
        if pad:
            res = ShardedSolveResult(
                *(f[:T] for f in res[:5]), res.iterations, res.unconverged)
        if batch_shape != (T,):
            res = ShardedSolveResult(
                *(f.reshape(batch_shape + f.shape[1:]) for f in res[:5]),
                res.iterations, res.unconverged)
        return res


def measured_nf_conductances_sharded(
        g: jax.Array, spec: CrossbarSpec,
        g_ref: jax.Array | None = None,
        v_in: jax.Array | None = None,
        maxiter: int = 4000,
        precision: SolverPrecision | str | None = None,
        ctx: ShardingCtx | None = None,
        tol: float = 1e-12,
        chain_impl: str = "lax") -> ShardedSolveResult:
    """Sharded circuit-measured NF of perturbed conductance fields.

    Scale-out twin of :func:`repro.crossbar.batched
    .measured_nf_conductances`: ``g`` is (..., J, K) per-cell
    conductances with arbitrary leading batch dims (the Monte-Carlo
    engine's ``(samples, tiles)`` axes land here flattened), ``g_ref``
    the matching clean conductances the NF is measured against.
    Non-divisible batches are padded with zero-drive tiles.
    """
    precision = resolve_precision(precision)
    if ctx is None or ctx.mesh is None:
        ctx = tile_sharding_ctx()
    mesh = ctx.mesh
    axes = _tile_axes(mesh, ctx.rules)
    if not axes:
        from repro.crossbar.batched import measured_nf_conductances
        res = measured_nf_conductances(g, spec, g_ref, v_in, maxiter,
                                       precision, chain_impl)
        return ShardedSolveResult(
            *res[:5], res.iterations,
            jnp.sum((~tile_converged(res, tol)).astype(jnp.int32)))
    n_shards = 1
    for a in axes:
        n_shards *= dict(mesh.shape)[a]

    with enable_x64():
        spec_arr = jnp.array([spec.r, spec.r_on, spec.r_off], jnp.float64)
        if v_in is None:
            v_in = jnp.full((g.shape[-2],), spec.v_read, jnp.float64)
        batch_shape = g.shape[:-2]
        flat = g.reshape((-1,) + g.shape[-2:]).astype(jnp.float64)
        # The reference field is materialised at the full ensemble shape
        # here (unlike the batched engine, which broadcasts inside its
        # jit): the shard_map in_specs slice ref and g along the same
        # flattened tile axis, and a per-shard (T, J, K) replica of an
        # unexpanded reference would cost *more* memory than the
        # ensemble slice whenever n_shards > n_samples.  Each device
        # ends up holding only its 1/n_shards slice.
        ref = flat if g_ref is None else jnp.broadcast_to(
            g_ref, g.shape).reshape(flat.shape).astype(jnp.float64)
        T, J = flat.shape[0], flat.shape[1]
        v = jnp.broadcast_to(
            v_in.astype(jnp.float64),
            (T, J) if v_in.ndim == 1 else v_in.shape
        ).reshape(T, J)

        pad = (-T) % n_shards
        if pad:
            zt = jnp.zeros((pad,) + flat.shape[1:], flat.dtype)
            flat = jnp.concatenate([flat, zt])
            ref = jnp.concatenate([ref, zt])
            v = jnp.concatenate([v, jnp.zeros((pad, J), v.dtype)])

        res = _sharded_solver_g(mesh, tuple(axes), maxiter, float(tol),
                                precision, chain_impl)(flat, ref, v,
                                                       spec_arr)
        if pad:
            res = ShardedSolveResult(
                *(f[:T] for f in res[:5]), res.iterations, res.unconverged)
        if batch_shape != (T,):
            res = ShardedSolveResult(
                *(f.reshape(batch_shape + f.shape[1:]) for f in res[:5]),
                res.iterations, res.unconverged)
        return res


def measured_nf_conductances_sharded_checked(
        g: jax.Array, spec: CrossbarSpec,
        g_ref: jax.Array | None = None,
        v_in: jax.Array | None = None,
        maxiter: int = 4000,
        precision: SolverPrecision | str | None = None,
        ctx: ShardingCtx | None = None,
        tol: float = 1e-12,
        chain_impl: str = "lax",
        escalate: bool = True):
    """:func:`measured_nf_conductances_sharded` + convergence watchdog.

    The sharded solve runs as-is (its post-loop psum already counts
    failures NaN-aware); any failed tiles are then escalated on the
    host through the single-device batched engine — the failure set is
    a handful of tiles by construction, so a sharded rerun would be all
    dispatch overhead.  Returns ``(ShardedSolveResult, SolverReport)``
    with escalated tiles patched in and the ``unconverged`` count
    recomputed.
    """
    precision = resolve_precision(precision)
    res = measured_nf_conductances_sharded(g, spec, g_ref, v_in, maxiter,
                                           precision, ctx, tol,
                                           chain_impl)
    with enable_x64():
        J, K = g.shape[-2], g.shape[-1]
        batch_shape = g.shape[:-2]
        flat = ShardedSolveResult(
            *(jnp.reshape(f, (-1,) + f.shape[len(batch_shape):])
              for f in res[:5]), res.iterations, res.unconverged)
        base = flat[:5] + (flat.iterations,)
        from repro.crossbar.batched import BatchedSolveResult
        bres = BatchedSolveResult(*base)
        if not escalate:
            conv = tile_converged(bres, tol)
            if len(batch_shape) != 1:
                conv = conv.reshape(batch_shape)
            report = SolverReport(conv, res.iterations, 0,
                                  jnp.sum(~conv))
            record_solver_report(report)
            return res, report

        spec_arr = jnp.array([spec.r, spec.r_on, spec.r_off],
                             jnp.float64)
        if v_in is None:
            v_in_eff = jnp.full((J,), spec.v_read, jnp.float64)
        else:
            v_in_eff = v_in
        flat_v = (v_in_eff.reshape((-1, v_in_eff.shape[-1]))
                  if v_in_eff.ndim > 1 else v_in_eff)
        g_flat = g.reshape(-1, J, K).astype(jnp.float64)
        g_ref_eff = g if g_ref is None else g_ref

        def rerun(idx, prec_e, chain_e, mi_e):
            v_e = flat_v[idx] if flat_v.ndim > 1 else flat_v
            return solve_conductances_batched(
                g_flat[idx], _ref_subset(g_ref_eff, g.shape, idx, J, K),
                v_e, spec_arr, mi_e, tol, precision=prec_e,
                chain_impl=chain_e)

        bres, report = _escalate_failed(bres, rerun, precision,
                                        chain_impl, maxiter, tol)
        res = ShardedSolveResult(
            *(f.reshape(batch_shape + f.shape[1:]) for f in bres[:5]),
            bres.iterations, report.n_failed.astype(jnp.int32))
        if len(batch_shape) != 1:
            report = report._replace(
                converged=report.converged.reshape(batch_shape))
        record_solver_report(report)
        return res, report
