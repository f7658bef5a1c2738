import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/initialisation: jax locks the device
#   count on first init.  The dry-run (and only the dry-run) runs with
#   512 placeholder host devices so the production meshes materialise.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step for
train_4k, prefill for prefill_32k, one decode step for decode_32k /
long_500k), lowers it with ShapeDtypeStruct inputs (no allocation),
compiles it for the production mesh, and records:

  * memory_analysis()        — proves the cell fits per-device HBM
  * cost_analysis()          — raw XLA totals (loop bodies counted once)
  * hlo_cost.analyze()       — trip-count-aware FLOPs/bytes/collective
  * roofline terms           — §Roofline inputs

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod both] [--force]
Results land in results/dryrun/<arch>__<shape>__<mesh>[__tag].json.
"""
import argparse
import json
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry as tm
from repro.cachedirs import setup_compile_cache
from repro.configs import SHAPES, arch_shape_cells, get_config
from repro.configs.base import MeshConfig, ModelConfig, ShapeConfig, TrainConfig
from repro.distributed.sharding import ShardingCtx, logical_spec
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline
from repro.models import model as M
from repro.models import schema as sch
from repro.serve.engine import make_decode_step, make_prefill
from repro.train.step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def param_counts(cfg: ModelConfig) -> dict:
    schema = sch.model_schema(cfg)
    leaves = jax.tree_util.tree_leaves_with_path(
        schema, is_leaf=lambda x: isinstance(x, sch.ParamSpec))
    total = expert = embed = 0
    for path, spec in leaves:
        n = 1
        for s in spec.shape:
            n *= s
        total += n
        key = jax.tree_util.keystr(path)
        if "ffn_we_" in key:
            expert += n
        if key.endswith("['embed']"):
            embed += n
    active = total - expert
    if cfg.n_experts:
        active += expert * cfg.n_experts_per_token / cfg.n_experts
    return {"total": total, "active": active, "embed": embed,
            "expert": expert}


def _ns(ctx: ShardingCtx, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, ctx: ShardingCtx):
    """(abstract_batch, shardings) for a train batch."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if cfg.frontend:
        batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), dt),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        dims = {"embeds": ("batch", "seq", "act_embed"),
                "labels": ("batch", "seq")}
    else:
        batch = {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}
        dims = {"tokens": ("batch", "seq")}
    shardings = {
        k: NamedSharding(ctx.mesh, logical_spec(batch[k].shape, dims[k],
                                                ctx.mesh, ctx.rules))
        for k in batch}
    return batch, shardings


def build_cell(cfg: ModelConfig, shape: ShapeConfig, ctx: ShardingCtx,
               tcfg: TrainConfig):
    """Returns (fn, args, in_shardings, donate) ready for jit().lower()."""
    a_params = sch.abstract_params(cfg)
    p_specs = sch.partition_specs(cfg, ctx)
    p_ns = _ns(ctx, p_specs)

    if shape.kind == "train":
        from repro.optim.adamw import abstract_opt_state, optimizer_partition_specs
        a_opt = abstract_opt_state(
            a_params, tcfg.grad_compression == "int8_ef")
        o_specs = optimizer_partition_specs(p_specs)
        o_ns = jax.tree_util.tree_map(
            lambda s: NamedSharding(ctx.mesh, s), o_specs,
            is_leaf=lambda x: isinstance(x, P))
        if a_opt.ef_error is not None:
            o_ns = o_ns._replace(ef_error=p_ns)
        a_batch, b_ns = batch_specs(cfg, shape, ctx)
        fn = make_train_step(cfg, tcfg, ctx)
        return fn, (a_params, a_opt, a_batch), (p_ns, o_ns, b_ns), (0, 1)

    B, S = shape.global_batch, shape.seq_len
    a_state = M.init_decode_state(cfg, B, S, abstract=True)
    s_specs = M.state_partition_specs(cfg, ctx, B, S)
    s_ns = _ns(ctx, s_specs)

    if shape.kind == "prefill":
        dt = jnp.dtype(cfg.dtype)
        if cfg.frontend:
            a_in = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
            in_dims = ("batch", "seq", "act_embed")
        else:
            a_in = jax.ShapeDtypeStruct((B, S), jnp.int32)
            in_dims = ("batch", "seq")
        in_ns = NamedSharding(ctx.mesh, logical_spec(a_in.shape, in_dims,
                                                     ctx.mesh, ctx.rules))
        prefill = make_prefill(cfg, ctx)
        fn = lambda p, st, x: prefill(p, st, x, jax.random.PRNGKey(0))  # reprolint: disable=RPL003 -- dry-run traces shapes only; the key value is never sampled from
        return fn, (a_params, a_state, a_in), (p_ns, s_ns, in_ns), (1,)

    # decode: one new token against a seq_len-deep cache
    a_tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_ns = NamedSharding(ctx.mesh, logical_spec((B,), ("batch",),
                                                  ctx.mesh, ctx.rules))
    decode = make_decode_step(cfg, ctx)
    fn = lambda p, st, t: decode(p, st, t, jax.random.PRNGKey(0))  # reprolint: disable=RPL003 -- dry-run traces shapes only; the key value is never sampled from
    return fn, (a_params, a_state, a_tok), (p_ns, s_ns, tok_ns), (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules: str = "default", tag: str = "",
             overrides: dict | None = None, save_hlo: bool = False,
             out_dir: str = RESULTS_DIR) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = ShardingCtx(mesh=mesh, rules_name=rules)
    tcfg = TrainConfig()
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    chips = int(mesh.devices.size)

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "rules": rules, "tag": tag,
           "overrides": {k: str(v) for k, v in (overrides or {}).items()},
           "chips": chips}
    t0 = tm.monotonic()
    try:
        fn, args, in_sh, donate = build_cell(cfg, shape, ctx, tcfg)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = tm.monotonic() - t0
            compiled = lowered.compile()
        t_compile = tm.monotonic() - t0 - t_lower
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        cost = hlo_cost.analyze(hlo)
        counts = param_counts(cfg)
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind != "decode" else 1)
        n = counts["active"] - counts["embed"]
        mf = (6 if shape.kind == "train" else 2) * n * tokens
        # The compiled module is the post-SPMD per-device program; scale
        # by chip count to express global FLOPs/bytes (the roofline terms
        # divide by chips again, so per-device semantics are preserved).
        roof = Roofline(flops=cost.flops * chips,
                        bytes_accessed=cost.bytes_accessed * chips,
                        coll_bytes=cost.collective_bytes * chips,
                        chips=chips, model_flops=mf,
                        coll_breakdown={k: v * chips for k, v in
                                        cost.coll_breakdown.items()})
        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
                "code_bytes": getattr(mem,
                                      "generated_code_size_in_bytes", None),
            },
            "xla_cost_analysis": {k: ca.get(k) for k in
                                  ("flops", "bytes accessed",
                                   "transcendentals") if k in ca},
            "params": counts,
            "roofline": roof.as_dict(),
            "loop_trip_counts": cost.loop_trip_counts[:32],
        })
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            hp = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}"
                              + (f"__{tag}" if tag else "") + ".hlo")
            with open(hp, "w") as f:
                f.write(hlo)
    except Exception as e:  # a failing cell is a bug; record it loudly
        rec.update({"ok": False, "error": repr(e),
                    "traceback": traceback.format_exc()})
    rec["wall_s"] = round(tm.monotonic() - t0, 2)

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}" \
        + (f"__{tag}" if tag else "") + ".json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["no", "yes", "both"],
                    default="no")
    ap.add_argument("--rules", default="default")
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    if args.all:
        cells = arch_shape_cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape (or --all) required")
        cells = [(args.arch, args.shape)]

    pods = {"no": [False], "yes": [True], "both": [False, True]}[
        args.multi_pod]
    for arch, shape in cells:
        for mp in pods:
            mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
            fname = os.path.join(
                args.out, f"{arch}__{shape}__{mesh_name}"
                + (f"__{args.tag}" if args.tag else "") + ".json")
            if os.path.exists(fname) and not args.force:
                print(f"[skip cached] {arch} {shape} {mesh_name}")
                continue
            rec = run_cell(arch, shape, mp, rules=args.rules, tag=args.tag,
                           save_hlo=args.save_hlo, out_dir=args.out)
            if rec["ok"]:
                r = rec["roofline"]
                print(f"[ok] {arch:20s} {shape:12s} {mesh_name:16s} "
                      f"compile={rec['compile_s']:7.1f}s "
                      f"peakMB={(rec['memory']['peak_bytes'] or 0)/1e6:9.1f} "
                      f"dom={r['dominant']:10s} "
                      f"roofline={r['roofline_fraction']:.3f}")
            else:
                print(f"[FAIL] {arch} {shape} {mesh_name}: {rec['error']}")


if __name__ == "__main__":
    main()
