"""Compiles for a described TPU v5e, with no chip attached.

The chip's compiler is installed here and compiles for a topology that
is described and not attached: what Mosaic or XLA:TPU would refuse on
the chip (block alignment, unsupported casts, VMEM, HBM) is refused
here, at no chip time.  Nothing runs, so these tests say nothing about
results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and each test worker imports
every test file.  The persistent compilation cache is off around these
compiles (an entry for a chip cannot be read back without one).
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import CimConfig
from repro.configs.phi3_mini_38b import CONFIG as PHI3
from repro.deploy.engine import DEPLOYABLE, _as_matrix, spec_from_config
from repro.kernels.cim_mvm import ops as cim_ops
from repro.kernels.cim_mvm.ops import CimDeployment, cim_mvm
from repro.kernels.flash_attention.ops import flash_attention_tpu

HBM_BYTES = 16 * 10 ** 9        # TPU v5e: 16 GB of HBM per chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: skip, do not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def steer_pallas(monkeypatch):
    """Make "auto" dispatch as it does on TPU (the host backend is CPU)."""
    real = cim_ops.resolve_impl
    monkeypatch.setattr(cim_ops, "resolve_impl", lambda impl="auto": (
        "pallas" if impl == "auto" else real(impl)))
    cim_mvm.clear_cache()
    yield
    cim_mvm.clear_cache()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _abstract_deployment(in_dim, out_dim, cfg, sharding, layers=None):
    """Shapes of a CimDeployment (stacked over ``layers`` if given)."""
    spec = spec_from_config(cfg)
    ti, tn = spec.grid(in_dim, out_dim)
    lead = () if layers is None else (layers,)
    S = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt,
                                               sharding=sharding)
    return CimDeployment(
        codes=S((ti * spec.rows, tn * spec.weights_per_tile), jnp.int16),
        pos=S((ti * spec.rows, tn), jnp.int32), scale=S((), jnp.float32),
        n_bits=spec.n_bits, wpt=spec.weights_per_tile, cols=spec.cols,
        eta=cfg.cim.eta, reversed_df=True, in_dim=in_dim,
        out_dim=out_dim)


def _abstract_cim(params, cfg, sharding):
    """Stacked CimDeployment shapes for every deployable projection."""
    cim = {}
    for slot, sub in params.items():
        if not slot.startswith("slot"):
            continue
        cim[slot] = {}
        for pname in DEPLOYABLE:
            if pname in sub:
                w = sub[pname]
                i, n = _as_matrix(pname, np.empty(w.shape[1:], np.int8)).shape
                cim[slot][pname] = _abstract_deployment(
                    i, n, cfg, sharding, layers=w.shape[0])
    return cim


def _hlo_instructions(text: str):
    """{computation: [(is_root, name, opcode, elements, fused callee)]}
    of an optimised HLO module's text (tuple-valued instructions left
    out)."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        m = re.match(r"\s*(ROOT )?%(\S+) = \w+\[([\d,]*)\](?:\{[^}]*\})? "
                     r"([\w-]+)\((.*)", line)
        if m and cur is not None:
            root, name, dims, op, rest = m.groups()
            n = int(np.prod([int(d) for d in dims.split(",") if d]))
            callee = re.search(r"calls=%([\w.\-]+)", rest)
            cur.append((bool(root), name, op, n,
                        callee.group(1) if callee else None))
    return comps


def _cache_sized(text: str, elements: int, ops: tuple) -> list[str]:
    """Instructions that write ``elements`` or more and are one of
    ``ops``: by opcode, by the root of the computation a fusion fuses,
    or by the opcodes XLA names a fusion after (``copy_bitcast_fusion``,
    ``constant_dynamic-update-slice_fusion``).  Instructions inside
    fused computations write nothing of their own and are skipped."""
    comps = _hlo_instructions(text)
    fused = {c for ins in comps.values() for *_, op, _, c in ins
             if op == "fusion"}
    root = {k: next((i[2] for i in v if i[0]), None)
            for k, v in comps.items()}
    found = []
    for comp, ins in comps.items():
        if comp in fused:
            continue
        for _, name, op, n, callee in ins:
            kinds = {op}
            if op == "fusion":
                kinds |= {root.get(callee)}
                kinds |= set(name.split(".")[0].split("_"))
            if n >= elements and kinds & set(ops):
                found.append(f"{comp}: {name} ({op}, {n} elements)")
    return found


CIM_CFG = PHI3.replace(cim=CimConfig(enabled=True, mode="mdm"))


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("in_dim,out_dim", [
    (3072, 3072),           # attention q/k/v/o
    (3072, 8192),           # MLP gate/up
    (8192, 3072),           # MLP down
])
def test_cim_mvm_pallas_compiles_at_phi3_widths(one_chip, in_dim, out_dim,
                                                m, precision):
    """Also under an ambient default_matmul_precision("highest"), which
    reaches every in-kernel dot that does not pin its own precision."""
    dep = _abstract_deployment(in_dim, out_dim, CIM_CFG, one_chip)
    x = jax.ShapeDtypeStruct((m, in_dim), jnp.bfloat16, sharding=one_chip)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(lambda x, d: cim_mvm(x, d, impl="pallas")
                           ).lower(x, dep).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "cim_mvm" in text           # the kernel's name survives


@pytest.mark.parametrize("sq,skv", [(256, 512), (1, 512)])
def test_flash_attention_compiles_at_head_dim_96(one_chip, sq, skv):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    f = jax.jit(lambda q, k, v, qp, kp: flash_attention_tpu(
        q, k, v, q_positions=qp, k_positions=kp, interpret=False))
    compiled = f.lower(S((8, sq, 32, 96), jnp.bfloat16),
                       S((8, skv, 32, 96), jnp.bfloat16),
                       S((8, skv, 32, 96), jnp.bfloat16),
                       S((sq,), jnp.int32), S((skv,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_continuous_decode_step_compiles_at_phi3_widths(one_chip,
                                                        steer_pallas):
    """One ContinuousEngine decode step, phi3-mini widths, 1 layer,
    capacity 8 over a 512-token ring, every projection on the kernel."""
    from repro.distributed.sharding import ShardingCtx
    from repro.models.model import init_decode_state, init_params
    from repro.serve.continuous import make_slot_decode

    cfg = CIM_CFG.replace(n_layers=1)
    capacity, max_seq = 8, 512
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cim = _abstract_cim(params, cfg, one_chip)
    state = init_decode_state(cfg, capacity, max_seq, abstract=True,
                              per_slot=True)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    step = jax.jit(make_slot_decode(cfg, ShardingCtx()), donate_argnums=(1,))
    compiled = step.lower(
        _abstract(params, one_chip), _abstract(state, one_chip),
        S((capacity,), jnp.int32), S((capacity, 2), jnp.uint32),
        S((capacity,), jnp.int32), S((capacity,), jnp.float32),
        cim).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"decode step needs {used / 1e9:.2f} GB"


@pytest.mark.parametrize("weights", ["digital", "cim"])
@pytest.mark.parametrize("capacity,max_seq", [
    (64, 1024),             # the batch cell: a 1024-slot ring
    (16, 2048),             # the chat cell: the 2047-slot window's ring
])
def test_slot_decode_updates_the_cache_in_place(one_chip, steer_pallas,
                                                capacity, max_seq, weights):
    """The slot-pool decode as ``ContinuousEngine`` compiles it (phi3-mini
    widths, 3 layers; digital projections, or every one on the kernel):
    the donated cache is written in place and read where it lies, so
    nothing of one layer's k or v size is copied, padded, transposed or
    slice-updated, and the program's scratch is a sliver of the state.
    On the kernel path the scratch also holds the layer's crossbar codes,
    which the kernel's operands slice out of the layer stack.
    ``serve_join`` and ``serve_evict`` compiled on the decode's pool
    format re-lay nothing of cache size either.  On the kernel path a bank of another tree (a
    per-weight ``gain`` in every deployment, served by the XLA path) is
    compiled held to the first bank's formats, and copies nothing of
    cache size."""
    import dataclasses

    from repro.distributed.sharding import ShardingCtx
    from repro.models.model import init_decode_state, init_params
    from repro.serve.continuous import make_slot_decode
    from repro.serve.kvcache import (
        compile_decode,
        compile_transform,
        serve_evict,
        serve_join,
    )

    cfg = CIM_CFG.replace(n_layers=3, sliding_window=2047)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    state = _abstract(init_decode_state(cfg, capacity, max_seq,
                                        abstract=True, per_slot=True),
                      one_chip)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cim = _abstract_cim(params, cfg, one_chip) if weights == "cim" else None
    decode = make_slot_decode(cfg, ShardingCtx())
    args = lambda cim: (
        _abstract(params, one_chip), state, S((capacity,), jnp.int32),
        S((capacity, 2), jnp.uint32), S((capacity,), jnp.int32),
        S((capacity,), jnp.float32), cim, None)
    compiled = compile_decode(decode, args(cim), sharding=one_chip)
    fmt = compiled.input_formats[0][1]
    assert compiled.output_formats[1] == fmt
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    layer_codes = 0 if cim is None else nbytes(
        [d.codes for d in cim["slot0_attn"].values()]) // cfg.n_layers
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.01 * nbytes(state) + layer_codes, (temp, layer_codes)
    layer_k = state["slot0_attn"]["k"].size // cfg.n_layers
    big = _cache_sized(compiled.as_text(), layer_k,
                       ("copy", "pad", "transpose", "dynamic-update-slice",
                        "dynamic-slice"))
    assert not big, big

    src = _abstract(init_decode_state(cfg, 1, max_seq, abstract=True,
                                      per_slot=True), one_chip)
    i32 = S((), jnp.int32)
    for fn, rest in ((serve_join, (src, i32, i32)), (serve_evict, (i32,))):
        prog = compile_transform(fn, (state, *rest), fmt, donate=(0,))
        assert fn.__name__ in prog.as_text()
        assert prog.output_formats == fmt
        big = _cache_sized(prog.as_text(), layer_k,
                           ("copy", "pad", "transpose"))
        assert not big, (fn.__name__, big)

    if cim is not None:
        gained = jax.tree.map(
            lambda d: dataclasses.replace(d, gain=S(d.codes.shape,
                                                    jnp.float32)),
            cim, is_leaf=lambda d: isinstance(d, CimDeployment))
        other = compile_decode(decode, args(gained), like=compiled)
        assert other.input_formats[0][1] == fmt
        assert other.output_formats == compiled.output_formats
        big = _cache_sized(other.as_text(), layer_k,
                           ("copy", "pad", "transpose",
                            "dynamic-update-slice", "dynamic-slice"))
        assert not big, big


def test_sharded_solver_compiles_for_four_chips(topo):
    """The sharded crossbar solver over a 4-chip tile mesh: its post-loop
    collectives must lower on XLA:TPU (64-bit max all-reduces do not).
    A small tile batch in f32 keeps the compile short; the collectives
    are the same at every size and precision."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.compat import enable_x64
    from repro.crossbar.batched import F32
    from repro.distributed.solver_shard import TILE_AXIS, _sharded_solver

    mesh = Mesh(np.asarray(topo.devices[:4]), (TILE_AXIS,))
    tiled, rep = NamedSharding(mesh, P(TILE_AXIS)), NamedSharding(mesh, P())
    with enable_x64():
        fn = _sharded_solver(mesh, (TILE_AXIS,), 4000, 1e-12, F32, "lax")
        compiled = fn.lower(
            jax.ShapeDtypeStruct((8, 16, 16), jnp.float32, sharding=tiled),
            jax.ShapeDtypeStruct((8, 16), jnp.float64, sharding=tiled),
            jax.ShapeDtypeStruct((3,), jnp.float64, sharding=rep)).compile()
    assert "all-reduce" in compiled.as_text()
