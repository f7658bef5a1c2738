"""Public jit'd wrapper for the fused CIM matmul, with backend dispatch.

``deploy()`` turns a dense weight matrix into a :class:`CimDeployment`
(signed quantisation codes + MDM physical-position table) once, at
deployment time; ``cim_mvm()`` then computes the PR-distorted matmul for
any activation batch.  When ``cfg.cim.enabled`` is set, the model zoo
(``repro.models.model``) routes attention/MLP projection matmuls through
``cim_mvm`` using deployments built by ``repro.deploy`` at engine init.

Dispatch (``impl``):

* ``"auto"`` (default) — the Pallas kernel on TPU, the fused XLA path
  (:mod:`repro.kernels.cim_mvm.xla`) everywhere else.  There is no
  capability probe: on TPU a kernel that does not compile raises.  The
  kernel's grid accumulation assumes TPU's sequential grid, so GPU stays
  on XLA (see :func:`resolve_impl`).  Interpret mode is **never**
  selected automatically: it executes the kernel body block-by-block in
  Python and is orders of magnitude too slow for serving.
* ``"pallas"`` / ``"xla"`` — force one production path.
* ``"interpret"`` — the Pallas kernel under ``pallas_call(interpret=
  True)``; test/validation only (bit-faithful BlockSpec checking).

Deployments carrying a per-weight ``gain``, a column permutation
(``col_pos``) or per-read noise are served by the XLA path under
``"auto"`` on every backend: the kernel has no operands for them.
:func:`served_impl` says which path a deployment gets.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.bitslice import codes_to_bits, quantize_magnitude
from repro.core.mdm import MdmPlan, plan_from_bits
from repro.core.noise import PAPER_ETA
from repro.core.tiling import CrossbarSpec
from repro.kernels.cim_mvm.kernel import cim_mvm_pallas
from repro.kernels.cim_mvm.xla import cim_mvm_xla
from repro.kernels.runtime import round_up
from repro.mapping import resolve_pipeline

IMPLS = ("auto", "pallas", "xla", "interpret")


@partial(jax.tree_util.register_dataclass,
         data_fields=("codes", "pos", "scale", "gain", "col_pos",
                      "degraded", "noise_tag"),
         meta_fields=("n_bits", "wpt", "cols", "eta", "reversed_df",
                      "in_dim", "out_dim", "sigma_read"))
@dataclasses.dataclass
class CimDeployment:
    """A weight matrix deployed onto bit-sliced crossbars.

    codes: (I_tiles*rows, N_tiles*wpt) int16 signed codes (sign*magnitude).
    pos:   (I_tiles*rows, N_tiles)     int32 physical row positions.
    scale: ()                          f32 quantisation scale.
    gain:  (I_tiles*rows, N_tiles*wpt) f32 per-weight conductance gain,
           or None (the ideal-device default).  Produced by
           ``repro.nonideal.inject`` to fold programming variation /
           drift into the deployment (stuck-at faults fold into the
           codes themselves); consumed by the fused XLA path only.
    col_pos: (I_tiles, N_tiles, cols) int32 physical bitline of each
           dataflow-layout column per tile, or None (identity column
           strategies — the pre-pipeline layout).  Produced by
           column-permuting mapping pipelines (e.g. the X-CHANGR-style
           bitline sort); consumed by the fused XLA path only.
    degraded: () int32 count of programmed active bits landing on OPEN
           (line-open) cells after the spare-line remap, or None (no
           fault injection).  ``degraded > 0`` means spare capacity ran
           out and this deployment's crossbar output is structurally
           wrong — the model layer (``repro.models.model._cim_matmul``)
           demotes such deployments to the digital matmul fallback.
    noise_tag: () int32 per-deployment PRNG tag (unique per deployed
           matrix), or None.  Folded into the caller-supplied read key
           so every deployment draws independent per-read noise from
           one shared key.
    sigma_read: relative per-read conductance noise std (static meta;
           the deployment-time :class:`repro.nonideal.models
           .NonidealModel.sigma_read`).  Applied by the fused XLA path
           only, and only when a read key is supplied to ``cim_mvm``.

    Registered as a pytree with the array fields as data, so stacked
    deployments (one per scanned model layer) thread through ``lax.scan``
    and ``jax.jit`` like any other parameter (a None gain/col_pos is an
    empty subtree and costs nothing).
    """

    codes: jax.Array
    pos: jax.Array
    scale: jax.Array
    n_bits: int
    wpt: int
    cols: int
    eta: float
    reversed_df: bool
    in_dim: int
    out_dim: int
    gain: jax.Array | None = None
    col_pos: jax.Array | None = None
    degraded: jax.Array | None = None
    noise_tag: jax.Array | None = None
    sigma_read: float = 0.0


def deploy(w: jax.Array, spec: CrossbarSpec, mode="mdm",
           eta: float = PAPER_ETA,
           plan: MdmPlan | None = None) -> tuple[CimDeployment, MdmPlan]:
    """Quantise, plan and package a weight matrix.

    ``mode`` is a :class:`repro.mapping.MappingPipeline` or a
    named/legacy string (``repro.mapping.resolve_pipeline``).  Pass
    ``plan`` (e.g. a cache hit or a slice of a fused whole-model plan
    from ``repro.deploy``) to skip the planning pass entirely; the bit
    planes are then never materialised — packaging needs only the int16
    codes and the plan's position tables.
    """
    if w.ndim != 2:
        raise ValueError("deploy expects (in_dim, out_dim)")
    I, N = w.shape
    codes, sign, scale = quantize_magnitude(w, spec.n_bits)
    if plan is None:
        plan = plan_from_bits(codes_to_bits(codes, spec.n_bits), scale,
                              spec, resolve_pipeline(mode))

    ti, tn = spec.grid(I, N)
    rows, wpt = spec.rows, spec.weights_per_tile
    i_pad, n_pad = ti * rows, tn * wpt
    signed = (codes.astype(jnp.int32) * sign.astype(jnp.int32)).astype(jnp.int16)
    signed = jnp.pad(signed, ((0, i_pad - I), (0, n_pad - N)))

    # pos[i, tn] = physical row position of input i in column-tile tn.
    qi = jnp.arange(i_pad) % rows
    tii = jnp.arange(i_pad) // rows
    pos = plan.row_position[tii, :, qi].astype(jnp.int32)      # (i_pad, tn)

    # The physical layout (dataflow direction, bitline permutation)
    # comes from the plan itself, so a supplied plan (cache hit / fused
    # whole-model slice) stays consistent even when ``mode`` disagrees.
    col_pos = (None if plan.col_position is None
               else plan.col_position.astype(jnp.int32))
    return CimDeployment(
        codes=signed, pos=pos, scale=scale, n_bits=spec.n_bits, wpt=wpt,
        cols=spec.cols, eta=float(eta),
        reversed_df=bool(plan.reversed_dataflow), in_dim=I, out_dim=N,
        col_pos=col_pos), plan


def _block_sizes(M: int, I: int, N: int, wpt: int) -> tuple[int, int, int]:
    """(BM, BI, BN) for the Pallas grid; I and N are the padded dims.

    Every block is (8, 128)-aligned or spans its whole axis, as Mosaic
    requires.  The position block is ``BN // wpt`` lanes wide, so BN is
    ``128 * wpt`` (or the whole width when narrower).
    """
    bm = min(round_up(M, 8), 256)
    bi = 256 if I >= 256 else round_up(I, 8)
    bn = min(N, 128 * wpt)
    return bm, bi, bn


def resolve_impl(impl: str = "auto") -> str:
    """Resolve ``"auto"`` to the production path for the active backend.

    Never returns ``"interpret"`` — interpret mode must be requested
    explicitly (tests/validation only).  ``"auto"`` is the Pallas kernel
    on TPU and the fused XLA path elsewhere: the kernel accumulates its
    output block across sequential grid steps (`out_ref[...] +=` with
    init at ki == 0), which is TPU grid semantics — on a GPU, parallel
    grid cells would race on that accumulator.  The backend is read at
    call time, so importing this module touches no device.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl={impl!r} not in {IMPLS}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def served_impl(dep: CimDeployment, impl: str = "auto",
                noisy: bool = False) -> str:
    """The path :func:`cim_mvm` takes for ``dep`` under ``impl``.

    ``noisy`` says whether the call carries per-read noise (a read key
    and a deployment with ``sigma_read > 0`` and a ``noise_tag``).
    Per-weight gain, per-tile column permutations and per-read noise
    live in the fused XLA expansion only, so ``"auto"`` sends such
    deployments there; an explicit ``"pallas"``/``"interpret"`` request
    for one raises instead of being rerouted (a TPU parity check would
    otherwise attribute XLA numbers to the kernel).
    """
    resolved = resolve_impl(impl)
    if resolved == "xla" or (dep.gain is None and dep.col_pos is None
                             and not noisy):
        return resolved
    if impl != "auto":
        what = ("a deployment gain" if dep.gain is not None
                else "a column-permuted deployment"
                if dep.col_pos is not None else "per-read noise")
        raise ValueError(
            f"impl={impl!r} cannot apply {what}; "
            "use impl='xla' (or 'auto') for such deployments")
    return "xla"


@partial(jax.jit, static_argnames=("impl",))
def cim_mvm(x: jax.Array, dep: CimDeployment,
            read_key: jax.Array | None = None,
            impl: str = "auto") -> jax.Array:
    """y = x @ W_effective for a CIM-deployed weight matrix.

    x: (..., in_dim); returns (..., out_dim) f32.  ``impl`` picks the
    execution path (see module docstring and :func:`served_impl`); the
    default dispatches to the Pallas kernel or the fused XLA path, never
    to interpret mode.

    ``read_key`` enables per-read conductance noise: when the
    deployment carries ``sigma_read > 0`` and a ``noise_tag``, the tag
    is folded into the key and fresh Gaussian weight noise is drawn for
    *this* read (decode steps pass a fresh key per step).  ``None``
    (the default) is bit-identical to the noiseless path.
    """
    noisy = (read_key is not None and dep.sigma_read > 0.0
             and dep.noise_tag is not None)
    impl = served_impl(dep, impl, noisy)
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    M, I = x2.shape
    if I != dep.in_dim:
        raise ValueError(f"x feature dim {I} != deployed in_dim {dep.in_dim}")

    i_pad, n_pad = dep.codes.shape

    if impl == "xla":
        x2 = jnp.pad(x2, ((0, 0), (0, i_pad - I)))
        rk = (jax.random.fold_in(read_key, dep.noise_tag) if noisy
              else None)
        y = cim_mvm_xla(x2, dep.codes, dep.pos, dep.scale,
                        n_bits=dep.n_bits, wpt=dep.wpt, cols=dep.cols,
                        eta=dep.eta, reversed_df=dep.reversed_df,
                        gain=dep.gain, col_pos=dep.col_pos,
                        read_key=rk, sigma_read=dep.sigma_read)
        return y[:, :dep.out_dim].reshape(*batch_shape, dep.out_dim)

    bm, bi, bn = _block_sizes(M, i_pad, n_pad, dep.wpt)
    mp, ip, np_ = round_up(M, bm), round_up(i_pad, bi), round_up(n_pad, bn)
    x2 = jnp.pad(x2, ((0, mp - M), (0, ip - I)))
    codes = jnp.pad(dep.codes, ((0, ip - i_pad), (0, np_ - n_pad)))
    pos = jnp.pad(dep.pos, ((0, ip - i_pad), (0, (np_ - n_pad) // dep.wpt)))

    y = cim_mvm_pallas(
        x2, codes, pos, jnp.asarray(dep.scale, jnp.float32).reshape(1, 1),
        n_bits=dep.n_bits, wpt=dep.wpt, cols=dep.cols, eta=dep.eta,
        reversed_df=dep.reversed_df, block_m=bm, block_n=bn, block_i=bi,
        interpret=impl == "interpret")
    return y[:M, :dep.out_dim].reshape(*batch_shape, dep.out_dim)
