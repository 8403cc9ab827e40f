import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first initialization, and the dry-run needs 512
placeholder host devices to build the production meshes.  (Only this
module does that: smoke tests and benchmarks see the real single device.)

For each cell this driver:

1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
2. resolves the sharding rules (logical axes -> mesh axes with
   divisibility fallbacks) for params / optimizer / batch / cache,
3. ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` — proving the
   distribution config is coherent (sharding propagation, collectives,
   layouts) without allocating anything,
4. records ``compiled.memory_analysis()`` (fits-per-device proof),
   ``cost_analysis()``, and the scan-corrected roofline terms
   (`repro.launch.roofline`) into a JSON artifact for EXPERIMENTS.md.

Usage::

    python -m repro.launch.dryrun --arch xlstm-125m --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --mesh both \
        --out artifacts/dryrun
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config
from ..models import steps as steps_mod
from ..models.sharding import ShardingRules
from ..optim import AdamWConfig, constant
from . import roofline as rl
from .mesh import make_production_mesh
from .specs import (SHAPES, cell_shardings, default_microbatches,
                    input_specs, skip_reason)

__all__ = ["run_cell", "main"]


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _build_step(cfg, kind: str, rules: ShardingRules, microbatches: int = 1,
                opt_cfg: AdamWConfig = AdamWConfig(),
                acc_dtype: str = "float32"):
    if kind == "train":
        return steps_mod.make_train_step(cfg, constant(3e-4), opt_cfg,
                                         rules=rules,
                                         microbatches=microbatches,
                                         acc_dtype=acc_dtype)
    if kind == "prefill":
        return steps_mod.make_prefill_step(cfg, rules=rules)
    return steps_mod.make_decode_step(cfg, rules=rules)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             save_hlo: Optional[str] = None,
             donate: bool = True, microbatches: Optional[int] = None,
             opt_cfg: AdamWConfig = AdamWConfig(),
             acc_dtype: str = "float32",
             cfg=None) -> Dict[str, Any]:
    cfg = cfg if cfg is not None else get_config(arch)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "family": cfg.family}
    reason = skip_reason(cfg, shape_name)
    if reason:
        rec["skipped"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rules = ShardingRules(mesh)
    kind, specs = input_specs(cfg, shape_name, opt_cfg)
    shardings = cell_shardings(cfg, rules, shape_name, opt_cfg)
    rec["kind"] = kind
    if microbatches is None:
        microbatches = default_microbatches(cfg, shape_name, rules)
    rec["microbatches"] = microbatches

    step = _build_step(cfg, kind, rules, microbatches, opt_cfg, acc_dtype)
    sp = SHAPES[shape_name]

    if kind == "train":
        args = (specs["state"], specs["batch"])
        in_sh = (_named(mesh, shardings["state"]),
                 _named(mesh, shardings["batch"]))
        metrics_struct = jax.eval_shape(step, *args)[1]
        out_sh = (in_sh[0], jax.tree.map(
            lambda _: NamedSharding(mesh, P()), metrics_struct))
        donate_argnums = (0,) if donate else ()
    elif kind == "prefill":
        args = (specs["params"], specs["batch"], specs["cache"])
        logits_spec = rules.spec(("batch", None, "vocab"),
                                 (sp.global_batch, 1, cfg.vocab))
        in_sh = (_named(mesh, shardings["params"]),
                 _named(mesh, shardings["batch"]),
                 _named(mesh, shardings["cache"]))
        out_sh = (NamedSharding(mesh, logits_spec), in_sh[2])
        donate_argnums = (2,) if donate else ()
    else:
        args = (specs["params"], specs["tokens"], specs["cache"])
        logits_spec = rules.spec(("batch", None, "vocab"),
                                 (sp.global_batch, 1, cfg.vocab))
        in_sh = (_named(mesh, shardings["params"]),
                 NamedSharding(mesh, shardings["tokens"]),
                 _named(mesh, shardings["cache"]))
        out_sh = (NamedSharding(mesh, logits_spec), in_sh[2])
        donate_argnums = (2,) if donate else ()

    t0 = time.time()
    lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate_argnums).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(f"[{arch} x {shape_name} x {mesh_name}] memory_analysis:", mem)
    ca = compiled.cost_analysis() or {}
    print(f"[{arch} x {shape_name} x {mesh_name}] cost_analysis flops:",
          ca.get("flops"), "bytes:", ca.get("bytes accessed"))

    rep = rl.analyze_compiled(compiled, n_devices=n_dev)
    mf = rl.model_flops(cfg, sp)
    per_dev_mf = mf / n_dev
    rec.update(
        t_lower_s=round(t_lower, 2), t_compile_s=round(t_compile, 2),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_bytes=mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        ),
        cost_analysis=dict(flops=ca.get("flops"),
                           bytes_accessed=ca.get("bytes accessed")),
        roofline=rep.as_dict(),
        model_flops_global=mf,
        model_flops_per_device=per_dev_mf,
        useful_flops_ratio=(per_dev_mf / rep.flops) if rep.flops else None,
        roofline_fraction=(per_dev_mf / rl.PEAK_FLOPS) / rep.t_bound
        if rep.t_bound else None,
    )
    if save_hlo:
        os.makedirs(os.path.dirname(save_hlo) or ".", exist_ok=True)
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())
        rec["hlo_path"] = save_hlo
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help=f"architecture id or 'all' ({ARCH_IDS})")
    ap.add_argument("--shape", default="all",
                    help=f"shape name or 'all' ({list(SHAPES)})")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--fail-fast", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="override the gradient-accumulation heuristic")
    ap.add_argument("--factored-opt", action="store_true",
                    help="Adafactor-style factored 2nd moment + bf16 mu")
    ap.add_argument("--acc-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="gradient-accumulation buffer dtype")
    args = ap.parse_args(argv)
    opt_cfg = AdamWConfig(factored_nu=args.factored_opt,
                          mu_dtype="bfloat16" if args.factored_opt
                          else "float32")

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                hlo = os.path.join(args.out, tag + ".hlo.txt") \
                    if args.save_hlo else None
                try:
                    rec = run_cell(arch, shape, multi_pod=mp, save_hlo=hlo,
                                   microbatches=args.microbatches,
                                   opt_cfg=opt_cfg,
                                   acc_dtype=args.acc_dtype)
                except Exception as e:        # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                    if args.fail_fast:
                        raise
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1, default=str)
                status = ("SKIP" if rec.get("skipped")
                          else "FAIL" if rec.get("error") else "OK")
                extra = ""
                if status == "OK":
                    peak = rec["memory"]["peak_bytes"] / 2**30
                    extra = (f" peak={peak:.2f}GiB "
                             f"bottleneck={rec['roofline']['bottleneck']} "
                             f"compile={rec['t_compile_s']}s")
                print(f"[{status}] {tag}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
