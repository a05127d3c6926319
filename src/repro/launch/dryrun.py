"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is how the distribution config is proven coherent without hardware:
512 placeholder host devices form the production meshes — (16,16) single
pod, (2,16,16) two pods — and every cell's step function must lower,
SPMD-partition and compile.  ``memory_analysis()`` proves the per-device
footprint; ``cost_analysis()`` + the HLO collective parse feed §Roofline.

The module pins its process to the CPU platform before jax is imported
(and appends its device-count flag to ``XLA_FLAGS``), so a dry run on a
machine with a chip never takes the chip.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import (SHAPES, ArchConfig, ShapeConfig, get_config,
                            get_shape, registry, shape_cells)
from ..models.model import input_specs, model_flops, param_count
from ..models.sharding import logical_to_pspec, param_pspecs, set_rules
from ..optim.adamw import AdamW
from ..optim.schedule import constant
from ..roofline.analysis import analyze_compiled
from ..train.step import (make_serve_step, make_train_step, train_state_shape)
from .mesh import make_production_mesh, rules_for

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# sharding specs for inputs and caches
# ---------------------------------------------------------------------------

def batch_pspec(name: str, spec) -> P:
    if name == "frames":
        return logical_to_pspec(("batch", None, None), spec.shape)
    return logical_to_pspec(("batch", None), spec.shape)


_CACHE_AXES = {
    # name -> logical axes, aligned to the *trailing* dims of the array
    "k": ("batch", "seq", "kv", "hd"),
    "v": ("batch", "seq", "kv", "hd"),
    "xk": ("batch", "seq", "kv", "hd"),
    "xv": ("batch", "seq", "kv", "hd"),
    "state": ("batch", "tp", None, None),      # (L,B,H,P,N)
    "conv": ("batch", None, "tp"),             # (L,B,K-1,conv_dim)
    "m_C": ("batch", None, "tp", None),        # (G,m,B,H,hd,hd)
    "m_n": ("batch", None, "tp"),              # (G,m,B,H,hd)
    "m_m": ("batch", None),                    # (G,m,B,H)
    "m_conv": ("batch", None, "tp"),           # (G,m,B,K-1,d_inner)
    "s_h": ("batch", "tp"), "s_c": ("batch", "tp"),
    "s_n": ("batch", "tp"), "s_m": ("batch", "tp"),
    "s_conv": ("batch", None, "tp"),           # (G,B,K-1,D)
}


def cache_pspecs(cfg: ArchConfig, cache_shapes, *, long_context: bool):
    """PartitionSpec tree for a decode cache.

    KV heads shard over 'model' when divisible, else the head_dim does;
    the cache sequence dim shards over 'data' only for long-context cells
    (batch already covers 'data' otherwise).
    """
    from ..models.sharding import axis_size
    tp_size = axis_size("model")

    def spec_for(path, arr):
        name = str(getattr(path[-1], "key", path[-1]))
        if name not in _CACHE_AXES:
            return P()
        axes = list(_CACHE_AXES[name])
        # resolve the kv/hd choice
        if "kv" in axes:
            kv_ok = tp_size > 0 and cfg.n_kv_heads % max(tp_size, 1) == 0
            axes[axes.index("kv")] = "tp" if kv_ok else None
            if not kv_ok:
                axes[axes.index("hd")] = "tp"
            else:
                axes[axes.index("hd")] = None
        if "seq" in axes:
            axes[axes.index("seq")] = "seq" if long_context else None
        pad = arr.ndim - len(axes)
        logical = (None,) * pad + tuple(axes)
        return logical_to_pspec(logical, arr.shape)

    return jax.tree_util.tree_map_with_path(spec_for, cache_shapes)


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
               remat: str = "dots", microbatch: int = 1,
               donate: bool = True, compress_pods: bool = False):
    """Build + lower + compile one cell. Returns (compiled, meta)."""
    long_ctx = shape.name == "long_500k"
    model_axis = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    rules = rules_for(cfg, model_axis=model_axis, seq_shard_cache=long_ctx)
    set_rules(rules)
    specs = input_specs(cfg, shape)
    meta: Dict[str, Any] = {"rules": {k: str(v) for k, v in rules.items()},
                            "remat": remat, "microbatch": microbatch,
                            "compress_pods": compress_pods}

    with jax.set_mesh(mesh):
        ns = lambda spec_tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree)
        if shape.kind == "train":
            optimizer = AdamW(lr=constant(1e-4))
            state_shapes = train_state_shape(cfg, optimizer)
            state_specs = param_pspecs(state_shapes)
            batch_specs = {k: batch_pspec(k, v) for k, v in specs.items()}
            step = make_train_step(cfg, optimizer, remat=remat,
                                   microbatch=microbatch,
                                   compress_pods=compress_pods, mesh=mesh)
            jf = jax.jit(step,
                         in_shardings=(ns(state_specs), ns(batch_specs)),
                         out_shardings=(ns(state_specs), None),
                         donate_argnums=(0,) if donate else ())
            lowered = jf.lower(state_shapes, specs)
        elif shape.kind == "prefill":
            from ..models.model import bundle_for
            bundle = bundle_for(cfg)
            params_shapes = jax.eval_shape(
                lambda k: bundle.init(cfg, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            params_specs = param_pspecs(params_shapes)
            batch_specs = {k: batch_pspec(k, v) for k, v in specs.items()}

            def prefill_fn(params, inputs):
                if cfg.family == "encdec":
                    return bundle.prefill(cfg, params, inputs,
                                          max_seq=shape.seq_len)
                return bundle.prefill(cfg, params, inputs["tokens"],
                                      max_seq=shape.seq_len)

            jf = jax.jit(prefill_fn,
                         in_shardings=(ns(params_specs), ns(batch_specs)))
            lowered = jf.lower(params_shapes, specs)
        else:  # decode
            from ..models.model import bundle_for
            bundle = bundle_for(cfg)
            params_shapes = jax.eval_shape(
                lambda k: bundle.init(cfg, k),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            params_specs = param_pspecs(params_shapes)
            cache_shapes = specs["cache"]
            cache_specs = cache_pspecs(cfg, cache_shapes,
                                       long_context=long_ctx)
            tok_spec = batch_pspec("tokens", specs["tokens"])
            serve_step = make_serve_step(cfg)
            jf = jax.jit(serve_step,
                         in_shardings=(ns(params_specs), ns(cache_specs),
                                       NamedSharding(mesh, tok_spec)),
                         out_shardings=(None, ns(cache_specs)),
                         donate_argnums=(1,) if donate else ())
            lowered = jf.lower(params_shapes, cache_shapes, specs["tokens"])

        t0 = time.time()
        compiled = lowered.compile()
        meta["compile_s"] = round(time.time() - t0, 2)
    return compiled, meta


def run_cell(arch_id: str, shape_name: str, mesh_name: str, *,
             remat: str = "dots", microbatch: int = 1,
             out_dir: Optional[str] = None, tag: str = "",
             compress_pods: bool = False,
             quiet: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch_id)
    shape = get_shape(shape_name)
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = int(mesh.devices.size)
    try:
        compiled, meta = lower_cell(cfg, shape, mesh, remat=remat,
                                    microbatch=microbatch,
                                    compress_pods=compress_pods)
        report = analyze_compiled(
            compiled, arch=arch_id, shape=shape_name,
            mesh_name=f"{'2x16x16' if multi else '16x16'}", chips=chips,
            model_flops=model_flops(cfg, shape),
            notes=f"remat={remat} mb={microbatch} {tag}")
        result = {"status": "ok", **report.to_json(), **meta,
                  "params": param_count(cfg),
                  "active_params": param_count(cfg, active_only=True)}
    except Exception as e:
        result = {"status": "error", "arch": arch_id, "shape": shape_name,
                  "mesh": "multi" if multi else "single",
                  "error": f"{type(e).__name__}: {e}",
                  "trace": traceback.format_exc()[-2000:]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch_id}__{shape_name}__{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1, default=str)
    if not quiet:
        if result["status"] == "ok":
            print(f"[OK]   {arch_id:24s} {shape_name:12s} {mesh_name:6s} "
                  f"compute={result['compute_s']:.4f}s "
                  f"memory={result['memory_s']:.4f}s "
                  f"coll={result['collective_s']:.4f}s "
                  f"dom={result['dominant']:10s} "
                  f"args/dev={result['argument_bytes']/1e9:.2f}GB "
                  f"temp/dev={result['temp_bytes']/1e9:.2f}GB "
                  f"compile={result.get('compile_s', 0)}s")
        else:
            print(f"[FAIL] {arch_id:24s} {shape_name:12s} {mesh_name:6s} "
                  f"{result['error']}")
    return result


def all_cells():
    for arch_id, cfg in registry().items():
        if arch_id == "lidc-demo":
            continue
        for shape_name in shape_cells(cfg):
            yield arch_id, shape_name


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape) cell")
    ap.add_argument("--remat", default="full",
                    choices=["none", "dots", "full"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--compress", action="store_true",
                    help="int8 cross-pod gradient compression (multi mesh)")
    ap.add_argument("--tag", default="", help="artifact suffix for variants")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for a, s in all_cells():
            print(f"{a:26s} {s}")
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    assert all(a and s for a, s in cells), "need --arch and --shape (or --all)"

    failures = 0
    for arch_id, shape_name in cells:
        for mesh_name in meshes:
            r = run_cell(arch_id, shape_name, mesh_name, remat=args.remat,
                         microbatch=args.microbatch, out_dir=args.out,
                         compress_pods=args.compress and mesh_name == "multi",
                         tag=args.tag)
            failures += r["status"] != "ok"
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
