import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (architecture x input-shape
x mesh) cell on the production mesh, with no device allocation
(ShapeDtypeStruct stand-ins everywhere).

Per cell this records, into experiments/dryrun/<arch>__<shape>__<mesh>.json:
  * compiled.memory_analysis()   — per-device bytes (proves it fits)
  * compiled.cost_analysis()     — HLO FLOPs / bytes for the roofline
  * collective bytes + op counts — parsed from the compiled SPMD HLO
  * wall compile time, input sharding summary

Usage:
  python -m repro.launch.dryrun --arch granite-34b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--resume]
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.models import registry
from repro.launch.mesh import dp_axes_for, make_production_mesh, mesh_axis_sizes
from repro.launch.hlo_cost import analyze_hlo
from repro.parallel.hints import with_hints
from repro.parallel.sharding import build_cache_specs, build_param_specs
from repro.train.optimizer import AdamWConfig, init_state

# per-arch tuned microbatch counts (EXPERIMENTS.md §Perf): kimi's FSDP
# weight gathers scale with the microbatch count, and its per-microbatch
# activations are small enough to halve it
TUNED_MICROBATCHES = {"kimi-k2-1t-a32b": 4}

ARTIFACT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "experiments", "dryrun",
)

# bytes per element for HLO shape parsing
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)"
                       r"\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the SPMD module.

    The compiled module is the per-device program, so these are
    bytes-per-chip."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        # HLO: "%x = TYPE[SHAPE] op-name(...)" or fusion lines; match ops
        for op in _COLLECTIVES:
            if f" {op}(" in line or f" {op}-start(" in line:
                lhs = line.split(f" {op}", 1)[0]
                b = _shape_bytes(lhs)
                stats[op]["count"] += 1
                stats[op]["bytes"] += b
                break
    stats["total_bytes"] = sum(
        v["bytes"] for k, v in stats.items() if isinstance(v, dict)
    )
    stats["total_count"] = sum(
        v["count"] for k, v in stats.items() if isinstance(v, dict)
    )
    return stats


def _mem_dict(ma) -> dict:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    return out


def build_cell(cfg, shape, mesh, *, num_microbatches: int = 8,
               fsdp: bool = True):
    """-> (fn, arg_shapes: tuple, in_shardings: tuple).

    Weight-sharding policy: ZeRO-1 by default (params TP-sharded over
    'model' only; optimizer states additionally sharded over the dp axes,
    costing one grad reduce-scatter + one param all-gather per step).
    Full FSDP (weights dp-sharded too, re-gathered per layer per
    microbatch) only when the per-model-shard weights exceed the HBM
    budget — i.e. kimi-k2's 1T params (129 GB per 16-way shard)."""
    bundle = registry.build(cfg)
    sizes = mesh_axis_sizes(mesh)
    dp = dp_axes_for(mesh, shape.global_batch)
    fsdp_axes = None
    if fsdp:
        fsdp_axes = ("pod", "data") if "pod" in sizes else ("data",)
    weights_per_shard = cfg.num_params() * 2 / sizes["model"]
    # > ~6 GB/chip forces FSDP — but only training carries optimizer
    # states; inference weights stay TP/EP-sharded (kimi: 8 GB/chip, fits)
    # so decode/prefill never pay per-layer weight gathers
    heavy = weights_per_shard > 6e9 and shape.kind == "train"
    # inference cells of over-budget MoE archs (kimi): 2-D expert sharding
    # (E over 'model', FFN dim over 'data') keeps weights resident
    expert_cols = (
        "data"
        if (cfg.moe and shape.kind != "train" and weights_per_shard > 6e9)
        else None
    )
    params_shape = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pspecs = build_param_specs(
        params_shape,
        n_experts=cfg.moe.n_experts if cfg.moe else 0,
        model_axis_size=sizes["model"],
        axis_sizes=sizes,
        fsdp_axes=fsdp_axes if heavy else None,
        expert_cols_axis=expert_cols,
    )
    opt_pspecs = build_param_specs(
        params_shape,
        n_experts=cfg.moe.n_experts if cfg.moe else 0,
        model_axis_size=sizes["model"],
        axis_sizes=sizes,
        fsdp_axes=fsdp_axes,  # ZeRO: optimizer states always fully sharded
    )
    sh = lambda spec: NamedSharding(mesh, spec)
    batch_specs = registry.input_specs(cfg, shape)

    def batch_spec_for(k, v):
        if k == "pos":
            return P()
        if dp is not None and v.shape[0] % _np(dp, sizes) == 0:
            return P(dp)
        return P()

    if shape.kind == "train":
        opt_cfg = AdamWConfig(state_dtype=cfg.opt_state_dtype)
        opt_shape = jax.eval_shape(lambda p: init_state(opt_cfg, p),
                                   params_shape)
        ospecs = {
            "m": opt_pspecs, "v": opt_pspecs, "step": P(),
        }
        mb = TUNED_MICROBATCHES.get(cfg.name, num_microbatches)
        if shape.global_batch % mb:
            mb = 1
        fn = bundle.make_train_step(opt_cfg, num_microbatches=mb,
                                    dp_axes=dp)
        args = (params_shape, opt_shape, batch_specs)
        in_sh = (
            jax.tree_util.tree_map(sh, pspecs),
            jax.tree_util.tree_map(sh, ospecs),
            {k: sh(batch_spec_for(k, v)) for k, v in batch_specs.items()},
        )
        return fn, args, in_sh

    if shape.kind == "prefill":
        fn_ = bundle.make_prefill_step()

        def fn(params, batch):
            return fn_(params, batch)

        args = (params_shape, batch_specs)
        in_sh = (
            jax.tree_util.tree_map(sh, pspecs),
            {k: sh(batch_spec_for(k, v)) for k, v in batch_specs.items()},
        )
        return fn, args, in_sh

    # decode
    b = shape.global_batch
    s_cache = shape.seq_len if cfg.family != "audio" else shape.seq_len // 4
    cache_shape = jax.eval_shape(lambda: bundle.cache_init(b, s_cache))
    cspecs = build_cache_specs(
        cache_shape, dp_axes=dp, n_kv_heads=cfg.n_kv_heads,
        model_axis_size=sizes["model"], axis_sizes=sizes,
    )
    dec = bundle.make_decode_step()
    specs = registry.input_specs(cfg, shape)

    def fn(params, token, cache, pos):
        return dec(params, token, cache, pos)

    args = (params_shape, specs["token"], cache_shape, specs["pos"])
    in_sh = (
        jax.tree_util.tree_map(sh, pspecs),
        sh(batch_spec_for("token", specs["token"])),
        jax.tree_util.tree_map(sh, cspecs),
        sh(P()),
    )
    return fn, args, in_sh


def _np(axes, sizes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, tuple):
        n = 1
        for a in axes:
            n *= sizes[a]
        return n
    return sizes[axes]


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             num_microbatches: int = 8, fsdp: bool = True,
             save: bool = True, sp_enable: bool = False) -> dict:
    cfg = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    fn, args, in_sh = build_cell(
        cfg, shape, mesh, num_microbatches=num_microbatches, fsdp=fsdp
    )
    sizes = mesh_axis_sizes(mesh)
    # sequence parallelism for full-sequence paths (train/prefill): the
    # residual stream shards its seq dim over the TP axis (DESIGN.md,
    # EXPERIMENTS.md §Perf granite iteration 1)
    # sp='model' (true sequence parallelism) measured WORSE for attention
    # archs (chunked-attn scan vs seq sharding, EXPERIMENTS.md §Perf it.1);
    # sp=None keeps the bf16 residual pin only. Opt back in via --sp.
    sp = (
        "model"
        if sp_enable
        and shape.kind in ("train", "prefill")
        and shape.seq_len % sizes["model"] == 0
        else None
    )
    dp = dp_axes_for(mesh, shape.global_batch)
    # explicit shard_map all-to-all MoE dispatch for heavy-MoE training
    # cells (kimi): EXPERIMENTS.md §Perf kimi it.5 — the dp->ep token
    # exchange at wire-minimum bytes. Inference kimi uses 2-D expert
    # sharding instead (different weight layout).
    cfg_ = ARCHS[arch_name]
    ep_ok = cfg_.moe and cfg_.moe.n_experts % sizes["model"] == 0
    heavy_ = cfg_.num_params() * 2 / sizes["model"] > 6e9
    use_a2a = bool(ep_ok and heavy_ and shape.kind == "train")
    fsdp_axes_ = ("pod", "data") if "pod" in sizes else ("data",)
    fn = with_hints(
        fn, ep="model", ep_size=sizes["model"], dp=dp,
        dp_size=_np(dp, sizes), sp=sp,
        a2a=mesh if use_a2a else None,
        fsdp=fsdp_axes_ if use_a2a else None,
    )
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    coll = collective_stats(hlo)
    # trip-count-corrected costs (XLA cost_analysis counts loop bodies once;
    # see repro.launch.hlo_cost)
    hc = analyze_hlo(hlo)
    art = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": int(mesh.devices.size),
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "num_microbatches": num_microbatches if shape.kind == "train" else 0,
        "fsdp": fsdp,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": _mem_dict(ma),
        "cost": {k: float(v) for k, v in ca.items()
                 if isinstance(v, (int, float))},
        "collectives": coll,
        "hlo_cost": {
            "flops": hc.flops,
            "coll_bytes": hc.coll_bytes,
            "coll_elems": hc.coll_elems,
            # deployment-dtype projection of the CPU-backend f32-promoted
            # collectives (see HloCost.coll_bytes_dtype)
            "coll_bytes_dtype": hc.coll_bytes_dtype(
                2 if cfg.dtype == "bfloat16" else 4
            ),
            "coll_counts": hc.coll_counts,
            "hbm_proxy_bytes": hc.hbm_proxy_bytes,
            "n_whiles": hc.n_whiles,
        },
        "model_params": cfg.num_params(),
        "active_params": cfg.active_params(),
    }
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(
            ARTIFACT_DIR, f"{arch_name}__{shape_name}__{mesh_name}.json"
        )
        with open(path, "w") as f:
            json.dump(art, f, indent=1)
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose artifact already exists")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="enable true sequence parallelism (see EXPERIMENTS.md)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    failures = []
    for a, s in cells:
        path = os.path.join(ARTIFACT_DIR, f"{a}__{s}__{mesh_name}.json")
        if args.resume and os.path.exists(path):
            print(f"[dryrun] skip (exists): {a} x {s} x {mesh_name}")
            continue
        print(f"[dryrun] {a} x {s} x {mesh_name} ...", flush=True)
        try:
            art = run_cell(a, s, multi_pod=args.multi_pod,
                           num_microbatches=args.microbatches,
                           fsdp=not args.no_fsdp, sp_enable=args.sp)
            if "skipped" in art:
                print(f"[dryrun]   SKIP: {art['skipped']}")
                continue
            mem = art["memory"]
            print(
                f"[dryrun]   ok: compile {art['compile_s']:.1f}s  "
                f"flops/dev {art['hlo_cost']['flops']:.3e}  "
                f"args/dev {mem.get('argument_size_in_bytes', 0)/2**30:.2f} GiB  "
                f"temp/dev {mem.get('temp_size_in_bytes', 0)/2**30:.2f} GiB  "
                f"coll/dev {art['hlo_cost']['coll_bytes']/2**30:.3f} GiB"
            )
        except Exception as e:
            failures.append((a, s, repr(e)))
            print(f"[dryrun]   FAIL: {e}")
            traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} x {s}: {e}")
        raise SystemExit(1)
    print("[dryrun] all cells compiled")


if __name__ == "__main__":
    main()
