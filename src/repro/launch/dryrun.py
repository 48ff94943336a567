import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers, SPMD-partitions, and compiles on the production mesh —
and extract the roofline terms from the compiled artifact.

The two lines above MUST run before any jax import (jax locks the device
count at first init); the env override exists so the test-suite subprocess
can request 8 fake devices instead of 512.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod|both]

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>[__<rules>].json and
feed EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse
import json
import pathlib
import time

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.launch.mesh import make_mesh_by_name
from repro.launch.steps import build_decode, build_prefill, build_train
from repro.models.model import Model
from repro.roofline.analysis import HW, model_flops_per_step, roofline_terms
from repro.roofline.hlo_cost import analyze_hlo
from repro.sharding.rules import EXPERT_PARALLEL_RULES, SERVE_RULES, TRAIN_RULES

RULE_SETS = {
    "train": TRAIN_RULES,
    "serve": SERVE_RULES,
    "expert_parallel": EXPERT_PARALLEL_RULES,
}


def should_skip(arch: str, shape_name: str) -> str | None:
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.supports_long_context():
        return (
            "full-attention architecture: 500k-token decode is outside the "
            "published family's attention form (see DESIGN.md §5)"
        )
    return None


def run_one(arch: str, shape_name: str, mesh_name: str, rules_name: str | None = None,
            out_dir: str = "artifacts/dryrun", verbose: bool = True,
            overrides: dict | None = None, tag: str = "") -> dict:
    import dataclasses

    cfg = get_config(arch)
    if overrides:
        typed = {}
        for k, v in overrides.items():
            cur = getattr(cfg, k)
            typed[k] = type(cur)(v) if cur is not None else v
        cfg = dataclasses.replace(cfg, **typed)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_mesh_by_name(mesh_name)
    model = Model(cfg)
    rules_name = rules_name or ("train" if shape.kind == "train" else "serve")
    rules = RULE_SETS[rules_name]

    t0 = time.time()
    if shape.kind == "train":
        step, (pshard, oshard, batch_sh), out_sh, (aparams, aopt) = build_train(model, mesh, rules)
        specs = model.input_specs(shape)
        bshard = batch_sh(specs)
        jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard), out_shardings=out_sh)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(aparams, aopt, specs)
    elif shape.kind == "prefill":
        step, (pshard, batch_sh), aparams = build_prefill(model, mesh, shape, rules)
        specs = model.input_specs(shape)
        bshard = batch_sh(specs)
        jitted = jax.jit(step, in_shardings=(pshard, bshard))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(aparams, specs)
    else:
        step, (pshard, cshard, tshard, lshard), (aparams, acache) = build_decode(model, mesh, shape, rules)
        specs = model.input_specs(shape)
        jitted = jax.jit(step, in_shardings=(pshard, cshard, tshard, lshard))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(aparams, specs["cache"], specs["token"], specs["cache_len"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # ---- analysis -------------------------------------------------------
    # HloCostAnalysis counts while bodies once; keep it for reference but use
    # the loop-aware analyzer (repro.roofline.hlo_cost) for the roofline.
    cost = compiled.cost_analysis() or {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # pragma: no cover - backend specific
        mem["error"] = str(e)

    hlo = compiled.as_text()
    loop_aware = analyze_hlo(hlo)
    flops = loop_aware.flops
    bytes_accessed = loop_aware.bytes
    colls = loop_aware.collectives
    n_chips = mesh.devices.size
    terms = roofline_terms(flops, bytes_accessed, colls)
    mf = model_flops_per_step(cfg, shape, n_chips)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mesh_shape": dict(mesh.shape),
        "rules": rules_name,
        "n_chips": n_chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collectives": colls,
        "xla_cost_analysis": {"flops": xla_flops, "bytes_accessed": xla_bytes},
        "memory": mem,
        "roofline": terms,
        "model_flops_per_chip": mf,
        "useful_flops_ratio": (mf / flops) if flops else None,
        "hlo_lines": hlo.count("\n"),
        "params_total": cfg.param_counts()[0],
        "params_active": cfg.param_counts()[1],
        "overrides": overrides or {},
        "tag": tag,
    }

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    suffix = f"__{rules_name}" if rules_name not in ("train", "serve") else ""
    if tag:
        suffix += f"__{tag}"
    path = out / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    path.write_text(json.dumps(record, indent=1))

    if verbose:
        r = terms
        print(
            f"[dryrun] {arch:18s} {shape_name:12s} {mesh_name:9s} {rules_name:15s} "
            f"compile={t_compile:6.1f}s flops/dev={flops:.3e} bytes/dev={bytes_accessed:.3e} "
            f"coll={r['collective_bytes']:.3e}B dom={r['dominant']:10s} "
            f"comp={r['compute_s']*1e3:.2f}ms mem={r['memory_s']*1e3:.2f}ms coll={r['collective_s']*1e3:.2f}ms",
            flush=True,
        )
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both", "test", "multitest"])
    ap.add_argument("--rules", default=None, choices=[None, *RULE_SETS], nargs="?")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--set", dest="sets", action="append", default=[],
                    help="config override key=value (repeatable), e.g. --set wkv_unroll=16")
    ap.add_argument("--tag", default="", help="artifact suffix for variant runs")
    args = ap.parse_args()
    overrides = dict(s.split("=", 1) for s in args.sets)

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_NAMES if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]

    failures = []
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                why = should_skip(arch, shape_name)
                if why:
                    print(f"[dryrun] {arch:18s} {shape_name:12s} SKIP: {why}", flush=True)
                    continue
                try:
                    run_one(arch, shape_name, mesh_name, args.rules, args.out,
                            overrides=overrides, tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape_name, mesh_name, repr(e)))
                    print(f"[dryrun] {arch:18s} {shape_name:12s} {mesh_name:9s} FAIL {e!r}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("[dryrun] all requested combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
