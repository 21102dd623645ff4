import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
512 placeholder CPU devices, prove the sharding config is coherent, and
extract memory / cost / collective-traffic analysis for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/]

Nothing is executed on devices: inputs are ShapeDtypeStructs; only
.lower().compile() runs. The two XLA_FLAGS lines above MUST stay the first
statements in this module (jax locks the device count at first init).
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.sharding import compat

import repro.configs as configs_lib
from repro.launch.input_specs import SHAPES, abstract_params, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.decode import decode_step
from repro.models.decode import prefill as prefill_fn
from repro.optim.optimizers import make_optimizer
from repro.roofline.hlo import parse_collectives, roofline_terms
from repro.runtime.steps import make_serve_step, make_train_step
from repro.sharding import specs as spec_lib
from repro.sharding.util import DP, filter_spec

# The chip the roofline terms model: the dry run compiles on host devices,
# so the kind is named here rather than read from jax.devices().
MODELED_KIND = "TPU v5 lite"

ARCHES = [
    "arctic-480b", "olmoe-1b-7b", "rwkv6-1.6b", "qwen3-14b",
    "command-r-35b", "phi3-medium-14b", "qwen3-8b",
    "seamless-m4t-large-v2", "qwen2-vl-72b", "recurrentgemma-9b",
]


def _ns(mesh, spec):
    return NamedSharding(mesh, filter_spec(spec, mesh.axis_names))


def _with_shardings(mesh, tree, spec_tree):
    """Attach NamedShardings to a ShapeDtypeStruct pytree."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=_ns(mesh, sp)),
        tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def build_cell(arch: str, shape: str, mesh, *, microbatches: int = 1,
               cfg_override=None):
    """Returns (fn, example_args) ready for jit(...).lower(*args)."""
    cfg = cfg_override if cfg_override is not None else configs_lib.get(arch)
    if SHAPES[shape]["kind"] != "train" and cfg.parallelism != "tp":
        # serving always uses TP: decode batches do not shard over 256+ ways
        cfg = dataclasses.replace(cfg, parallelism="tp")
    spec = input_specs(cfg, shape)
    params_abs = abstract_params(cfg)
    if spec["kind"] == "decode":
        # serving checkpoints are bf16 (deployment dtype; halves weight HBM)
        params_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape,
                jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
            params_abs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    pspec = spec_lib.param_spec(params_abs, cfg.parallelism)
    if (cfg.fsdp or cfg.parallelism == "fsdp") and spec["kind"] == "train":
        # ZeRO-3/FSDP: params also sharded over DP (all-gathered per layer)
        pspec = spec_lib.zero1_spec(pspec, params_abs, mesh,
                                    axes=cfg.dp_axes)
    params_in = _with_shardings(mesh, params_abs, pspec)

    if spec["kind"] == "train":
        opt = make_optimizer(cfg.optimizer)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        ospec = jax.tree.map(
            lambda _: P(), opt_abs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        # ZeRO-1: state sharded over DP on top of the param's TP sharding.
        ospec = {
            k: spec_lib.zero1_spec(
                spec_lib.param_spec(v, cfg.parallelism), v, mesh,
                axes=cfg.dp_axes)
            for k, v in opt_abs.items()
        }
        opt_in = _with_shardings(mesh, opt_abs, ospec)
        bspec = spec_lib.batch_spec(spec["batch"], mesh, axes=cfg.dp_axes)
        batch_in = _with_shardings(mesh, spec["batch"], bspec)
        step_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=_ns(mesh, P()))
        fn = make_train_step(cfg, opt, microbatches=microbatches)
        jfn = jax.jit(fn, donate_argnums=(0, 1))
        return jfn, (params_in, opt_in, batch_in, step_in)

    if spec["kind"] == "prefill":
        s_max = spec["s_max"]
        bspec = spec_lib.batch_spec(spec["batch"], mesh)
        batch_in = _with_shardings(mesh, spec["batch"], bspec)

        def fn(params, batch):
            return prefill_fn(params, cfg, s_max=s_max, **batch)

        return jax.jit(fn), (params_in, batch_in)

    # decode
    caches_abs = spec["caches"]
    cspec = spec_lib.cache_spec(caches_abs, mesh)
    caches_in = _with_shardings(mesh, caches_abs, cspec)
    tokens_in = jax.ShapeDtypeStruct(
        spec["tokens"].shape, spec["tokens"].dtype,
        sharding=_ns(mesh, spec_lib.divisible_spec(
            P(DP), spec["tokens"].shape, mesh)))
    pos_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=_ns(mesh, P()))
    fn = make_serve_step(cfg)
    return jax.jit(fn, donate_argnums=(1,)), \
        (params_in, caches_in, tokens_in, pos_in)


def _cost_tuple(compiled):
    cost = compat.cost_analysis(compiled)
    coll = parse_collectives(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll.wire_bytes), float(coll.operand_bytes),
            coll.by_kind(), len(coll.ops))


def extract_costs(arch: str, shape: str, mesh, *, microbatches: int = 1):
    """FLOPs/bytes/collective traffic by L-extrapolation.

    XLA's cost model counts while-loop bodies ONCE (trip counts unknown), so
    the full scanned lowering undercounts by ~num_layers x. We therefore
    lower small UNROLLED variants (scan_layers=False, unroll_inner=True —
    numerically identical control-flow changes) at L = unit and L = 2*unit
    layers, and extrapolate: total = non_layer + (L/unit) * delta. Hybrid
    patterns use the pattern length as the unit, plus a remainder lowering.
    """
    cfg = configs_lib.get(arch)
    unit = len(cfg.pattern) if cfg.family == "griffin" and cfg.pattern else 1
    L = cfg.num_layers
    rem = L % unit

    def reduced(nl):
        kw = dict(num_layers=nl, scan_layers=False, unroll_inner=True)
        if cfg.family == "encdec":
            kw["encoder_layers"] = nl
        return dataclasses.replace(cfg, **kw)

    def lower_cost(c):
        # ALWAYS microbatches=1 here: the grad-accumulation lax.scan would
        # hide (mb-1)/mb of the per-step cost from cost_analysis. Per-step
        # flops/bytes are microbatch-invariant; the full-L compile keeps the
        # real microbatch count for the memory analysis.
        jfn, args = build_cell(arch, shape, mesh, microbatches=1,
                               cfg_override=c)
        return _cost_tuple(jfn.lower(*args).compile())

    c1 = lower_cost(reduced(unit))
    c2 = lower_cost(reduced(2 * unit))
    delta = tuple(b - a for a, b in zip(c1[:4], c2[:4]))
    n_units = L // unit
    total = [a - d + n_units * d for a, d in zip(c1[:4], delta)]
    if rem:
        crem = lower_cost(reduced(2 * unit + rem))
        delta_rem = tuple(b - a for a, b in zip(c2[:4], crem[:4]))
        total = [t + dr for t, dr in zip(total, delta_rem)]
    return {"flops": total[0], "hbm_bytes": total[1],
            "wire_bytes": total[2], "operand_bytes": total[3],
            "per_unit": {"flops": delta[0], "hbm_bytes": delta[1],
                         "wire_bytes": delta[2]},
            "non_layer": {"flops": c1[0] - delta[0],
                          "hbm_bytes": c1[1] - delta[1],
                          "wire_bytes": c1[2] - delta[2]},
            "collective_by_kind_unit2": c2[4]}


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_dir: Path,
             microbatches: int = 1, tag: str = "",
             skip_full: bool = False, skip_cost: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    cfg = configs_lib.get(arch)
    t0 = time.time()
    with compat.use_mesh(mesh):
        if skip_full:
            mem = None
            t_lower = t_compile = 0.0
        else:
            jfn, args = build_cell(arch, shape, mesh,
                                   microbatches=microbatches)
            lowered = jfn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
        if skip_cost:
            costs = {"flops": 0.0, "hbm_bytes": 0.0, "wire_bytes": 0.0,
                     "operand_bytes": 0.0, "per_unit": {}, "non_layer": {},
                     "collective_by_kind_unit2": {}}
        else:
            costs = extract_costs(arch, shape, mesh,
                                  microbatches=microbatches)

    flops = costs["flops"]
    hbm_bytes = costs["hbm_bytes"]
    terms = roofline_terms(flops, hbm_bytes, costs["wire_bytes"],
                           MODELED_KIND)
    model_flops = 6.0 * cfg.active_param_count() \
        * SHAPES[shape]["batch"] * SHAPES[shape]["seq"]
    if SHAPES[shape]["kind"] == "decode":
        model_flops = 6.0 * cfg.active_param_count() * SHAPES[shape]["batch"]
    if SHAPES[shape]["kind"] == "prefill":
        model_flops = 2.0 * cfg.active_param_count() \
            * SHAPES[shape]["batch"] * SHAPES[shape]["seq"]
    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": flops,
            "hbm_bytes": hbm_bytes,
            "collective_wire_bytes": costs["wire_bytes"],
            "collective_operand_bytes": costs["operand_bytes"],
            "collective_by_kind_unit2": costs["collective_by_kind_unit2"],
            "per_unit": costs["per_unit"],
            "non_layer": costs["non_layer"],
        },
        "roofline": terms,
        "model_flops_global": model_flops,
        "model_flops_per_device": model_flops / n_chips,
        "useful_flop_ratio": (model_flops / n_chips) / flops if flops else 0.0,
    }
    if mem is not None:
        result["per_device"].update({
            "peak_memory_bytes": int(mem.temp_size_in_bytes
                                     + mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     - mem.alias_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
        })
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape}__{result['mesh']}{tag}.json"
    (out_dir / name).write_text(json.dumps(result, indent=2))
    return result


def run_fit_cell(name: str, *, multi_pod: bool, out_dir: Path, tag: str = ""):
    from repro.launch.fit_cell import CELLS, build_fit_cell
    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = CELLS[name]
    with compat.use_mesh(mesh):
        built = build_fit_cell(name, mesh)
        result = {"cell": f"admm_{name}", "m": spec["m"], "n": spec["n"],
                  "dtype": str(spec["dtype"].__name__),
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "chips": mesh.size, "status": "ok"}
        for phase, (jfn, args_) in built.items():
            t0 = time.time()
            compiled = jfn.lower(*args_).compile()
            mem = compiled.memory_analysis()
            cost = compat.cost_analysis(compiled)
            coll = parse_collectives(compiled.as_text())
            flops = float(cost.get("flops", 0.0))
            hbm = float(cost.get("bytes accessed", 0.0))
            terms = roofline_terms(flops, hbm, coll.wire_bytes,
                                   MODELED_KIND)
            result[phase] = {
                "compile_s": round(time.time() - t0, 1),
                "flops": flops, "hbm_bytes": hbm,
                "collective_wire_bytes": coll.wire_bytes,
                "collective_by_kind": coll.by_kind(),
                "peak_memory_bytes": int(mem.temp_size_in_bytes
                                         + mem.argument_size_in_bytes
                                         + mem.output_size_in_bytes
                                         - mem.alias_size_in_bytes),
                "roofline": terms,
            }
            t = terms
            print(f"[OK] admm_{name}:{phase} x {result['mesh']}: "
                  f"bottleneck={t['bottleneck']} "
                  f"compute={t['compute_s']*1e3:.2f}ms "
                  f"mem={t['memory_s']*1e3:.2f}ms "
                  f"coll={t['collective_s']*1e3:.3f}ms "
                  f"peak={result[phase]['peak_memory_bytes']/2**30:.2f}GiB",
                  flush=True)
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"admm_{name}__{result['mesh']}{tag}.json").write_text(
            json.dumps(result, indent=2))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--cost-only", action="store_true",
                    help="skip the full-L compile (roofline terms only)")
    ap.add_argument("--no-cost", action="store_true",
                    help="full-L compile proof only (multi-pod pass)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--fit-cell", default="",
                    help="ADMM fit cell: star_f32|star_bf16|fig1_bf16")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. sp_collectives=False)")
    args = ap.parse_args()
    if args.set:
        import repro.configs as _c
        _orig_get = _c.get

        def _patched(name):
            cfg = _orig_get(name)
            kv = {}
            for item in args.set:
                k, v = item.split("=", 1)
                cur = getattr(cfg, k)
                if isinstance(cur, bool):
                    v = v.lower() in ("1", "true", "yes")
                elif isinstance(cur, int):
                    v = int(v)
                elif isinstance(cur, float):
                    v = float(v)
                kv[k] = v
            return dataclasses.replace(cfg, **kv)

        _c.get = _patched
        configs_lib.get = _patched
    out_dir = Path(args.out)

    if args.fit_cell:
        run_fit_cell(args.fit_cell, multi_pod=args.multi_pod,
                     out_dir=out_dir, tag=args.tag)
        return

    cells = []
    if args.all:
        for arch in ARCHES:
            cfg = configs_lib.get(arch)
            for shape in SHAPES:
                if shape in cfg.skip_shapes:
                    continue
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                r = run_cell(arch, shape, multi_pod=mp, out_dir=out_dir,
                             microbatches=args.microbatches, tag=args.tag,
                             skip_full=args.cost_only,
                             skip_cost=args.no_cost)
                t = r["roofline"]
                print(f"[OK] {label}: compile={r['compile_s']}s "
                      f"bottleneck={t['bottleneck']} "
                      f"compute={t['compute_s']:.4f}s "
                      f"mem={t['memory_s']:.4f}s "
                      f"coll={t['collective_s']:.4f}s "
                      f"peak_mem={r['per_device'].get('peak_memory_bytes', 0)/2**30:.2f}GiB "
                      f"useful={r['useful_flop_ratio']:.2f}",
                      flush=True)
            except Exception as e:
                failures += 1
                out_dir.mkdir(parents=True, exist_ok=True)
                name = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}{args.tag}.FAILED.json"
                (out_dir / name).write_text(json.dumps(
                    {"arch": arch, "shape": shape, "status": "failed",
                     "error": traceback.format_exc()}, indent=2))
                print(f"[FAIL] {label}: {e}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
