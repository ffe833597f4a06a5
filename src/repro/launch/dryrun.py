import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede every other import (jax locks the device
count on first init).  For each cell this driver:

1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
2. constructs abstract params / optimizer state / inputs
   (ShapeDtypeStruct — no allocation),
3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
4. prints ``memory_analysis()`` / ``cost_analysis()`` and writes the
   roofline terms (incl. parsed collective bytes) to
   ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Usage:
    python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import SHAPES, all_configs, get_config  # noqa: E402
from ..distributed.sharding import (cache_specs, data_specs, param_specs,
                                    simple_batch_spec)  # noqa: E402
from ..perf.analytic import cell_cost  # noqa: E402
from ..perf.roofline import (TARGET_KIND, extract,  # noqa: E402
                              model_flops_for)
from ..train.optimizer import AdamW  # noqa: E402
from .compile_cache import enable_compile_cache  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .steps import (abstract_caches, abstract_opt_state, abstract_params,
                    input_specs, make_prefill_step, make_serve_step,
                    make_train_step)  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def mesh_size_hint(multi_pod: bool) -> int:
    return 512 if multi_pod else 256


def _sh(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               strategy: str = None, seq_shard: bool = False,
               remat_policy=None, accum_steps: int = 1,
               verbose: bool = True):
    """Lower + compile one cell; returns (compiled, meta dict).

    ``strategy`` selects the sharding configuration ("tp" baseline /
    "dp" pure-DP+ZeRO, see distributed.sharding); ``seq_shard`` puts the
    sequence dim of the hidden states on the "model" axis (sequence
    parallelism) — §Perf hillclimb candidates.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name not in cfg.shapes:
        raise ValueError(f"{arch} skips {shape_name} (see DESIGN.md §4)")
    if strategy is None:
        # §Perf-selected defaults: ZeRO/FSDP hurts decode latency, TP hurts
        # dense train throughput (full log in EXPERIMENTS.md §Perf).
        # Dense single-pod training goes pure-DP; multi-pod keeps TP so the
        # model axis stays productive when the batch cannot cover 512 ways.
        strategy = "tp" if shape.kind == "train" else "serve"
        if shape.kind == "train" and cfg.n_experts == 0 and not multi_pod \
                and shape.global_batch % mesh_size_hint(multi_pod) == 0:
            strategy = "dp"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    specs = input_specs(cfg, shape)
    params_abs = abstract_params(cfg)
    pspecs = param_specs(params_abs, mesh, strategy)

    t0 = time.perf_counter()
    if shape.kind == "train":
        opt = AdamW()
        opt_abs = abstract_opt_state(opt, params_abs)
        ospecs = param_specs(opt_abs, mesh, strategy)
        bspecs = data_specs(mesh, shape.global_batch, strategy)
        bspec = simple_batch_spec(mesh, shape.global_batch, strategy)
        seq_ax = "model" if (seq_shard and "model" in mesh.shape
                             and "model" not in (bspec[0] or ())) else None
        act_spec = NamedSharding(
            mesh, P(bspec[0] if len(bspec) else None, seq_ax, None))
        step = make_train_step(cfg, opt, act_spec=act_spec,
                               remat_policy=remat_policy,
                               accum_steps=accum_steps)
        jitted = jax.jit(
            step,
            in_shardings=(_sh(mesh, pspecs), _sh(mesh, ospecs),
                          _sh(mesh, bspecs)),
            out_shardings=(_sh(mesh, pspecs), _sh(mesh, ospecs),
                           NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, opt_abs, specs["batch"])
    elif shape.kind == "prefill":
        bspecs = data_specs(mesh, shape.global_batch, strategy)
        cspecs = cache_specs(cfg, mesh, shape.global_batch)
        step = make_prefill_step(cfg)
        logit_spec = simple_batch_spec(mesh, shape.global_batch, strategy)
        jitted = jax.jit(
            step,
            in_shardings=(_sh(mesh, pspecs),
                          {"inputs": NamedSharding(mesh, bspecs["inputs"])}),
            out_shardings=(NamedSharding(mesh, logit_spec),
                           _sh(mesh, cspecs)),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, specs["batch"])
    else:  # decode
        cspecs = cache_specs(cfg, mesh, shape.global_batch)
        tok_spec = simple_batch_spec(mesh, shape.global_batch, strategy)
        step = make_serve_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(_sh(mesh, pspecs), _sh(mesh, cspecs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, tok_spec),
                           _sh(mesh, cspecs)),
            donate_argnums=(1,),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, specs["caches"],
                                   specs["token"], specs["index"])
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    terms = extract(compiled, n_dev,
                    model_flops=model_flops_for(cfg, shape),
                    analytic=cell_cost(cfg, shape,
                                       remat_policy=remat_policy),
                    device_kind=TARGET_KIND)
    mem = compiled.memory_analysis()
    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "strategy": strategy, "seq_shard": seq_shard,
        "n_devices": n_dev,
        "lower_s": t_lower, "compile_s": t_compile,
        "memory": _mem_dict(mem),
        **terms.as_dict(),
    }
    if verbose:
        print(f"== {arch} x {shape_name} x {meta['mesh']} ==")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"   memory_analysis: {meta['memory']}")
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        print(f"   cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"   roofline: compute={terms.compute_s * 1e3:.2f}ms "
              f"memory={terms.memory_s * 1e3:.2f}ms "
              f"collective={terms.collective_s * 1e3:.2f}ms "
              f"dominant={terms.dominant} "
              f"fraction={terms.roofline_fraction:.3f}")
    return compiled, meta


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    if not out:
        out["repr"] = str(mem)
    return out


def run_cells(cells, multi_pod: bool, skip_existing: bool) -> int:
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch, shape_name in cells:
        mesh_tag = "2x16x16" if multi_pod else "16x16"
        out = OUT_DIR / f"{arch}__{shape_name}__{mesh_tag}.json"
        if skip_existing and out.exists():
            print(f"-- skip existing {out.name}")
            continue
        try:
            _, meta = lower_cell(arch, shape_name, multi_pod=multi_pod)
            out.write_text(json.dumps(meta, indent=1))
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"!! FAILED {arch} x {shape_name} x {mesh_tag}: {e}")
            traceback.print_exc()
    return failures


def all_cells():
    cells = []
    for arch, cfg in sorted(all_configs().items()):
        for shape_name in cfg.shapes:
            cells.append((arch, shape_name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    failures = 0
    if args.both_meshes:
        failures += run_cells(cells, False, args.skip_existing)
        failures += run_cells(cells, True, args.skip_existing)
    else:
        failures += run_cells(cells, args.multi_pod, args.skip_existing)
    print(f"dry-run complete: {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
