import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape) cell on the
production meshes, extract memory/cost/roofline terms.

The two lines above MUST precede any jax import: jax locks the device
count at first backend init, and the dry-run needs 512 placeholder CPU
devices to build the 2×16×16 production mesh. Tests/benchmarks import
this module never — they see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
Results are cached as JSON under results/dryrun/ (one file per cell) so
the full sweep is resumable.
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from ..configs.base import SHAPES, cells, load_arch  # noqa: E402
from ..core.backend import use_compile_cache  # noqa: E402
from ..core.cost_model import (  # noqa: E402
    TPU_HBM_BW,
    TPU_ICI_BW,
    TPU_PEAK_BF16_FLOPS,
)
from ..optim.adamw import AdamWConfig  # noqa: E402
from ..train.step import TrainConfig  # noqa: E402
from .hlo_analysis import analysis_dict  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .specs import build_cell  # noqa: E402

# Per-arch baseline policies: FSDP (weights' embed dim sharded over 'data')
# for models whose fp32 replicated-state would not fit 16 GB/chip;
# int8 optimizer moments for the 1T MoE.
FSDP_ARCHS = {"h2o_danube3_4b", "stablelm_12b", "qwen2_72b",
              "deepseek_v2_lite_16b", "kimi_k2_1t_a32b", "llava_next_34b"}
QUANT_OPT_ARCHS = {"kimi_k2_1t_a32b", "qwen2_72b"}


def make_tcfg(arch: str, *, quant_opt=None, microbatches=1,
              grad_compress=False) -> TrainConfig:
    q = (arch in QUANT_OPT_ARCHS) if quant_opt is None else quant_opt
    return TrainConfig(
        opt=AdamWConfig(quantized_state=q),
        microbatches=microbatches,
        cross_pod_grad_dtype="bfloat16" if grad_compress else "float32",
    )


def make_rules(arch: str, shape_name: str, mesh, *, fsdp=None,
               pure_dp: bool = False, cache_seq_shard: bool = False,
               seq_shard: bool = False):
    from ..sharding.rules import default_rules
    from .specs import data_axes
    overrides = {}
    if pure_dp:
        # small-model mode: no TP at all — the whole mesh is data-parallel
        # (weights replicated), batch sharded over every axis.
        all_axes = tuple(mesh.axis_names)
        overrides.update({"mlp": None, "heads": None, "kv_heads": None,
                          "vocab": None, "expert": None, "ssm_inner": None,
                          "act_heads": None, "batch": all_axes,
                          "groups": all_axes})
    use_fsdp = (arch in FSDP_ARCHS) if fsdp is None else fsdp
    if use_fsdp:
        overrides["embed"] = data_axes(mesh)
    shape = SHAPES[shape_name]
    dp = 1
    for a in data_axes(mesh):
        dp *= mesh.shape[a]
    if shape.global_batch % dp or shape.global_batch < dp:
        overrides["batch"] = None
        overrides["kv_seq"] = "data"
    if seq_shard:
        # Megatron-style sequence parallelism: the residual stream (and its
        # per-layer remat saves) shard over 'model'; GSPMD inserts the
        # all-gather/reduce-scatter pairs around attention/MLP.
        overrides["seq"] = "model"
    if cache_seq_shard and shape.kind == "decode":
        # shard the KV-cache sequence dim over 'model' (sequence
        # parallelism for the cache): GSPMD turns the per-step softmax
        # into a partial-softmax + reduction
        overrides["kv_seq"] = "model"
    return default_rules(**overrides).for_mesh(mesh)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             fsdp=None, quant_opt=None, grad_compress=False,
             microbatches: int = 1, serve_quant: bool = False,
             save_hlo: str = "", rules=None, tag: str = "",
             remat: str = "", q_chunk: int = 0, pure_dp: bool = False,
             attn_blocking: str = "", scores_dtype: str = "",
             cache_seq_shard: bool = False, kv_dtype: str = "",
             seq_shard: bool = False) -> dict:
    cfg = load_arch(arch).full()
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if q_chunk:
        cfg = dataclasses.replace(cfg, q_chunk=q_chunk)
    if attn_blocking:
        cfg = dataclasses.replace(cfg, attn_blocking=attn_blocking)
    if scores_dtype:
        cfg = dataclasses.replace(cfg, scores_dtype=scores_dtype)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    tcfg = make_tcfg(arch, quant_opt=quant_opt, microbatches=microbatches,
                     grad_compress=grad_compress)
    if kv_dtype:
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    if rules is None:
        rules = make_rules(arch, shape_name, mesh, fsdp=fsdp,
                           pure_dp=pure_dp, cache_seq_shard=cache_seq_shard,
                           seq_shard=seq_shard)

    t0 = time.time()
    with jax.set_mesh(mesh):
        cell = build_cell(cfg, shape, mesh, tcfg=tcfg, rules=rules,
                          serve_quant=serve_quant)
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          donate_argnums=cell.donate_argnums).lower(
            *cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    hlo = analysis_dict(text)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(text)

    # roofline terms (per-chip quantities; shapes in the partitioned module
    # are already per-device)
    compute_s = hlo["flops"] / TPU_PEAK_BF16_FLOPS
    memory_s = hlo["traffic_bytes"] / TPU_HBM_BW
    collective_s = hlo["collective_total"] / TPU_ICI_BW
    terms = dict(compute_s=compute_s, memory_s=memory_s,
                 collective_s=collective_s)
    dominant = max(terms, key=terms.get)

    # useful-FLOP ratio: MODEL_FLOPS vs compiled FLOPs (global)
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    hlo_flops_global = hlo["flops"] * chips
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    bound_time = max(terms.values())
    roofline_fraction = (model_flops / TPU_PEAK_BF16_FLOPS / chips) \
        / bound_time if bound_time else 0.0

    # memory-roofline efficiency: ideal HBM time = reading the live bytes
    # (weights + caches + batch) exactly once per step. This is the honest
    # roofline for decode (which can never be compute-bound).
    def _tree_bytes(t):
        return sum(l.dtype.itemsize * int(__import__("math").prod(l.shape))
                   for l in jax.tree.leaves(t)
                   if hasattr(l, "shape") and hasattr(l, "dtype"))

    live_bytes = sum(_tree_bytes(a) for a in cell.args)
    ideal_memory_s = live_bytes / chips / TPU_HBM_BW
    mem_efficiency = ideal_memory_s / memory_s if memory_s else 0.0
    if shape.kind == "decode":
        roofline_fraction = ideal_memory_s / bound_time if bound_time else 0.0

    out = dict(
        arch=arch, shape=shape_name, kind=shape.kind, tag=tag,
        multi_pod=multi_pod, chips=chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            code_bytes=mem.generated_code_size_in_bytes,
            total_per_chip=mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes,
        ),
        xla_cost=dict(flops=cost.get("flops"),
                      bytes_accessed=cost.get("bytes accessed")),
        hlo=hlo,
        roofline=dict(**terms, dominant=dominant,
                      model_flops=model_flops,
                      hlo_flops_global=hlo_flops_global,
                      useful_flop_ratio=useful,
                      ideal_memory_s=ideal_memory_s,
                      mem_efficiency=mem_efficiency,
                      roofline_fraction=roofline_fraction),
        params_total=cfg.param_count(),
        params_active=n_active,
    )
    return out


def cell_path(outdir, arch, shape_name, multi_pod, tag=""):
    mp = "pod2" if multi_pod else "pod1"
    suffix = f"_{tag}" if tag else ""
    return os.path.join(outdir, f"{arch}__{shape_name}__{mp}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--fsdp", default=None, type=lambda s: s == "1")
    ap.add_argument("--quant-opt", default=None, type=lambda s: s == "1")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--serve-quant", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--remat", default="")
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--attn-blocking", default="")
    ap.add_argument("--scores-dtype", default="")
    ap.add_argument("--cache-seq-shard", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--kv-dtype", default="")
    ap.add_argument("--q-chunk", type=int, default=0)
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    os.makedirs(args.out, exist_ok=True)
    todo = []
    pods = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod]
    if args.all:
        for arch, shape_name, skip in cells():
            for mp in pods:
                todo.append((arch, shape_name, mp))
    else:
        for mp in pods:
            todo.append((args.arch, args.shape, mp))

    failures = []
    for arch, shape_name, mp in todo:
        path = cell_path(args.out, arch, shape_name, mp, args.tag)
        if os.path.exists(path) and not args.force:
            print(f"[skip cached] {path}")
            continue
        label = f"{arch} × {shape_name} × {'2pod' if mp else '1pod'}"
        print(f"=== {label} ===", flush=True)
        try:
            res = run_cell(arch, shape_name, multi_pod=mp, fsdp=args.fsdp,
                           quant_opt=args.quant_opt,
                           grad_compress=args.grad_compress,
                           microbatches=args.microbatches,
                           serve_quant=args.serve_quant,
                           save_hlo=args.save_hlo, tag=args.tag,
                           remat=args.remat, q_chunk=args.q_chunk,
                           pure_dp=args.pure_dp,
                           attn_blocking=args.attn_blocking,
                           scores_dtype=args.scores_dtype,
                           cache_seq_shard=args.cache_seq_shard,
                           kv_dtype=args.kv_dtype, seq_shard=args.seq_shard)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            r = res["roofline"]
            print(f"  lower {res['lower_s']}s compile {res['compile_s']}s | "
                  f"mem/chip {res['memory']['total_per_chip']/2**30:.2f} GiB | "
                  f"compute {r['compute_s']*1e3:.2f}ms mem {r['memory_s']*1e3:.2f}ms "
                  f"coll {r['collective_s']*1e3:.2f}ms -> {r['dominant']} | "
                  f"roofline {r['roofline_fraction']:.3f}", flush=True)
        except Exception as e:
            failures.append((label, repr(e)))
            print(f"  FAILED: {e}\n{traceback.format_exc()}", flush=True)
    if failures:
        print("\nFAILURES:")
        for l, e in failures:
            print(f"  {l}: {e}")
        raise SystemExit(1)
    print("\nALL CELLS OK")


if __name__ == "__main__":
    main()
