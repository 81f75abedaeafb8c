import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The lines above MUST precede every other import — jax locks the device
count at first initialization. 512 host devices back both the single-pod
16×16 mesh (first 256) and the multi-pod 2×16×16 mesh.

Per cell this driver:
  1. builds ShapeDtypeStruct inputs (launch/specs.py — no allocation),
  2. jits the step with explicit in/out shardings from the logical-axis
     rule table (dist/sharding.py),
  3. ``.lower().compile()`` — success proves the sharding config is
     coherent (no GSPMD conflicts, no unsupported collectives),
  4. records ``memory_analysis()`` (per-device bytes — the "fits in 16 GB"
     proof), ``cost_analysis()``, and loop-aware HLO accounting
     (launch/hlo_analysis.py) → FLOPs + collective wire bytes,
  5. writes results/dryrun/<arch>__<shape>__<mesh>.json (+ .hlo.txt.gz).

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all                  # full 40-cell matrix
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, SHAPES, get_config, shape_cells
from repro.dist import sharding as shd
from repro.launch import hlo_analysis
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (batch_specs, choose_microbatches, decode_specs,
                                params_specs)
from repro.models.model import Model
from repro.train.optimizer import AdamW, AdamWConfig
from repro.train.serve import make_decode_step, make_prefill_step
from repro.train.step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s/link


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def build_lowerable(arch: str, shape_name: str, *, multi_pod: bool,
                    strategy: str = "2d", microbatches: int | None = None,
                    donate: bool = True, bf16_cotangent: bool = False,
                    serve_dtype: str | None = None,
                    param_dtype: str | None = None):
    """Returns (jitted, args, meta) ready to lower inside the mesh context."""
    cfg = get_config(arch)
    if bf16_cotangent:
        cfg = cfg.replace(bf16_cotangent=True)
    if param_dtype:
        cfg = cfg.replace(param_dtype=param_dtype)
    if strategy == "fsdp":
        cfg = cfg.replace(iota_embed=True)  # gather replicates at dp=256
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = shd.make_rules(mesh, strategy=strategy)
    model = Model(cfg)

    pspec = params_specs(cfg)
    if strategy == "fsdp":
        # batch shards over the WHOLE mesh under fsdp — microbatch choice
        # must see the full width or the model axis idles (15× redundant
        # compute measured on qwen2 with the 16-shard assumption)
        pass
    if serve_dtype and shape.kind in ("prefill", "decode"):
        dt = jnp.dtype(serve_dtype)
        pspec = type(pspec)(
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, dt if jnp.issubdtype(s.dtype, jnp.floating)
                else s.dtype), pspec.args),
            pspec.axes)
    p_sh = shd.tree_shardings(pspec.args, pspec.axes, mesh, rules)
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    if strategy == "fsdp":
        data_shards = mesh.size
    meta = {"arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
            "strategy": strategy, "kind": shape.kind,
            "bf16_cotangent": bf16_cotangent, "serve_dtype": serve_dtype,
            "num_params": cfg.num_params(),
            "num_active_params": cfg.num_active_params()}

    if shape.kind == "train":
        mb = microbatches or choose_microbatches(cfg, shape,
                                                 data_shards=data_shards)
        meta["microbatches"] = mb
        opt = AdamW(AdamWConfig(
            state_dtype="bfloat16" if cfg.param_dtype == "bfloat16"
            else "float32"))
        opt_shapes = jax.eval_shape(opt.init, pspec.args)
        opt_axes = opt.state_axes(pspec.axes)
        o_sh = shd.tree_shardings(opt_shapes, opt_axes, mesh, rules)
        bspec = batch_specs(cfg, shape, with_labels=True)
        b_sh = shd.tree_shardings(bspec.args, bspec.axes, mesh, rules)
        micro_axes = jax.tree.map(
            lambda ax: (None, *ax), bspec.axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        micro_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (mb, s.shape[0] // mb, *s.shape[1:]), s.dtype), bspec.args)
        micro_sh = (shd.tree_shardings(micro_shapes, micro_axes, mesh, rules)
                    if mb > 1 else None)
        step = make_train_step(model, opt, microbatches=mb,
                               microbatch_shardings=micro_sh)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1) if donate else ())
        args = (pspec.args, opt_shapes, bspec.args)
    elif shape.kind == "prefill":
        bspec = batch_specs(cfg, shape, with_labels=False)
        b_sh = shd.tree_shardings(bspec.args, bspec.axes, mesh, rules)
        cspec = decode_specs(cfg, shape)["cache"]
        c_sh = shd.tree_shardings(cspec.args, cspec.axes, mesh, rules)
        logits_sh = shd.sharding_for(
            (shape.global_batch, 1, cfg.padded_vocab),
            (shd.BATCH, None, shd.VOCAB), mesh, rules)
        step = make_prefill_step(model, cache_len=shape.seq_len)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                         out_shardings=(logits_sh, c_sh))
        args = (pspec.args, bspec.args)
    else:  # decode
        specs = decode_specs(cfg, shape)
        c_sh = shd.tree_shardings(specs["cache"].args, specs["cache"].axes,
                                  mesh, rules)
        t_sh = shd.sharding_for(specs["token"].args.shape,
                                specs["token"].axes, mesh, rules)
        pos_sh = shd.sharding_for((), (), mesh, rules)

        decode = make_decode_step(model)

        def serve_step(params, cache, token, pos):
            nxt, cache, logits = decode(params, cache, token, pos)
            return nxt, cache

        jitted = jax.jit(serve_step,
                         in_shardings=(p_sh, c_sh, t_sh, pos_sh),
                         out_shardings=(t_sh, c_sh),
                         donate_argnums=(1,) if donate else ())
        args = (pspec.args, specs["cache"].args, specs["token"].args,
                specs["pos"].args)
    return mesh, rules, jitted, args, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             strategy: str = "2d", microbatches: int | None = None,
             save_hlo: bool = True, out_dir: str | None = None,
             bf16_cotangent: bool = False, serve_dtype: str | None = None,
             param_dtype: str | None = None, tag: str = "") -> dict:
    t0 = time.time()
    mesh, rules, jitted, args, meta = build_lowerable(
        arch, shape_name, multi_pod=multi_pod, strategy=strategy,
        microbatches=microbatches, bf16_cotangent=bf16_cotangent,
        serve_dtype=serve_dtype, param_dtype=param_dtype)
    world = mesh.size
    with mesh, shd.activation_sharding(mesh, rules):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = hlo_analysis.xla_cost_analysis(compiled)
    hlo = compiled.as_text()
    stats = hlo_analysis.analyze(hlo, world=world)

    record = dict(meta)
    record.update({
        "world": world,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed") if k in cost},
        "hlo": {
            "dot_flops_per_device": stats.dot_flops,
            "conv_flops_per_device": stats.conv_flops,
            "dot_bytes_per_device": stats.dot_bytes,
            "collective_wire_bytes_per_device": stats.collective_bytes,
            "collective_by_kind": stats.collective_by_kind,
            "collective_sites": stats.collective_count,
            "while_trips": stats.while_trips,
        },
    })
    record["roofline"] = roofline_terms(record)
    if out_dir is None:
        out_dir = os.path.abspath(RESULTS_DIR)
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch}__{shape_name}__{record['mesh']}"
    if strategy != "2d":
        stem += f"__{strategy}"
    if tag:
        stem += f"__{tag}"
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    if save_hlo:
        with gzip.open(os.path.join(out_dir, stem + ".hlo.txt.gz"), "wt") as f:
            f.write(hlo)
    return record


def roofline_terms(record: dict) -> dict:
    """Three per-step roofline terms in seconds (per chip; SPMD — every chip
    does the same)."""
    flops_dev = record["hlo"]["dot_flops_per_device"]
    # HBM term: cost_analysis 'bytes accessed' counts scan bodies once, so
    # take the max with the loop-aware dot traffic (weights+activations of
    # every matmul × trip counts) and the per-step argument/output traffic.
    mem = record["memory"]
    bytes_dev = max(
        record["cost_analysis"].get("bytes accessed") or 0.0,
        record["hlo"].get("dot_bytes_per_device") or 0.0,
        float(mem["argument_bytes"]) + float(mem["output_bytes"]))
    coll_dev = record["hlo"]["collective_wire_bytes_per_device"]
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    dominant = max(
        ("compute", compute_s), ("memory", memory_s),
        ("collective", collective_s), key=lambda kv: kv[1])[0]
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dominant}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="full matrix: every arch × shape × both meshes")
    ap.add_argument("--strategy", default="2d", choices=("2d", "fsdp", "serve"))
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--bf16-cotangent", action="store_true")
    ap.add_argument("--serve-dtype", default=None, choices=(None, "bfloat16"))
    ap.add_argument("--param-dtype", default=None, choices=(None, "bfloat16"))
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in shape_cells(arch):
                cells.append((arch, shape, False))
                cells.append((arch, shape, True))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape, args.multi_pod))

    failures = []
    for arch, shape, mp in cells:
        tag = f"{arch} × {shape} × {_mesh_tag(mp)}"
        try:
            rec = run_cell(arch, shape, multi_pod=mp, strategy=args.strategy,
                           microbatches=args.microbatches,
                           save_hlo=not args.no_hlo, out_dir=args.out_dir,
                           bf16_cotangent=args.bf16_cotangent,
                           serve_dtype=args.serve_dtype,
                           param_dtype=args.param_dtype, tag=args.tag)
            r = rec["roofline"]
            print(f"OK   {tag}: compile={rec['compile_s']:.1f}s "
                  f"peak={rec['memory']['peak_estimate_bytes']/2**30:.2f}GiB "
                  f"compute={r['compute_s']*1e3:.2f}ms "
                  f"mem={r['memory_s']*1e3:.2f}ms "
                  f"coll={r['collective_s']*1e3:.2f}ms "
                  f"dom={r['dominant']}", flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue
            failures.append((tag, repr(e)))
            print(f"FAIL {tag}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print(f"\nall {len(cells)} cells OK")


if __name__ == "__main__":
    main()
