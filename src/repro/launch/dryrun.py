import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
# ^ MUST precede any jax init: jax locks the device count on first use.  Only
# when run as a script: importing the HLO helpers below (tests, benchmarks)
# must not turn the importing process into a 512-device host.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod or 2x16x16 multi-pod),
  2. materializes abstract, sharded param/optimizer/batch structs
     (ShapeDtypeStruct only — no allocation),
  3. jit-lowers the train/prefill/serve step and COMPILES it,
  4. records memory_analysis(), cost_analysis(), and the collective schedule
     parsed from the post-SPMD HLO, into dryrun_artifacts/<cell>.json.

EXPERIMENTS.md §Dry-run / §Roofline are generated from these artifacts
(benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out dryrun_artifacts]
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, SHAPES_BY_NAME, get_config, list_archs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, param_specs, rules_for_shape
from repro.launch.steps import (
    abstract_train_state,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.configs.base import TrainConfig, OptimizerConfig
from repro.sharding.partition import sharding_tree, use_rules

SDS = jax.ShapeDtypeStruct

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# bytes-on-the-wire multiplier per result byte (ring algorithms, large N)
_WIRE_FACTOR = {
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape token in an HLO result type."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


_OP_RE = re.compile(
    r"=\s*(?P<type>\(?[a-z0-9\[\],{}\s/#_\.]*?\)?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<async>-start|-done)?\("
)


def parse_collectives(hlo_text: str) -> dict:
    """Per-collective-kind result bytes + wire-byte model from post-SPMD HLO.

    Sync ops contribute their result bytes; async '-start' ops carry an
    (operand, result) tuple type, so their byte count is halved; '-done' ops
    are skipped (the start already counted the transfer).
    """
    out = {k: {"count": 0, "result_bytes": 0, "wire_bytes": 0.0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group("async") == "-done":
            continue
        kind = m.group("op")
        b = _shape_bytes(m.group("type"))
        if m.group("async") == "-start":
            b //= 2
        out[kind]["count"] += 1
        out[kind]["result_bytes"] += b
        out[kind]["wire_bytes"] += b * _WIRE_FACTOR[kind]
    out["total_wire_bytes"] = sum(
        v["wire_bytes"] for v in out.values() if isinstance(v, dict)
    )
    return out


def _mem_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "host_argument_size_in_bytes",
        "host_output_size_in_bytes",
        "host_temp_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def probe_layers(cfg, n_steps: int):
    """Config with the layer stack truncated to n_steps scan iterations.

    Used to correct XLA cost analysis, which counts a while-loop body ONCE
    regardless of trip count: lowering at 1 and 2 scan steps gives
    (outside, per-step) costs by differencing, and the full-depth cost is
    outside + per-step * trips (benchmarks/roofline.py)."""
    import dataclasses as dc

    kw = dict(unroll_layers=True)  # whole point: per-layer cost is countable
    if cfg.family == "hybrid":
        return dc.replace(cfg, num_layers=cfg.attn_period * n_steps, **kw)
    if cfg.family == "ssm":
        return dc.replace(cfg, num_layers=cfg.ssm.slstm_every * n_steps, **kw)
    if cfg.family == "vlm":
        return dc.replace(cfg, num_layers=cfg.cross_attn_period * n_steps, **kw)
    if cfg.family == "encdec":
        return dc.replace(cfg, num_layers=n_steps, encoder_layers=n_steps, **kw)
    return dc.replace(cfg, num_layers=n_steps, **kw)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             force: bool = False, rules_override=None, tag: str = "",
             cfg_override=None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}{tag}"
    art = out_dir / f"{cell_id}.json"
    if art.exists() and not force:
        return json.loads(art.read_text())

    cfg = cfg_override or get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    if not ok:
        record.update(status="skipped", reason=why)
        art.write_text(json.dumps(record, indent=2))
        return record

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_override or rules_for_shape(mesh, shape)
    from repro.launch.variants import apply_variant

    variant = tag[3:] if tag.startswith("__v") else None
    cfg, rules, tcfg_over = apply_variant(variant and variant.lstrip("_"), cfg, rules)
    try:
        with use_rules(rules, mesh):
            pspecs, paxes = param_specs(cfg, mesh, rules)
            ins = input_specs(cfg, shape, mesh, rules)
            if shape.kind == "train":
                tcfg = TrainConfig(
                    optimizer=OptimizerConfig(
                        master_weights=(arch != "jamba-1.5-large-398b")
                    ),
                    **tcfg_over,
                )
                step = make_train_step(cfg, tcfg)
                state, state_axes = abstract_train_state(cfg, tcfg.optimizer)
                sh = sharding_tree(state_axes, rules, mesh, shapes=state)
                state = jax.tree_util.tree_map(
                    lambda s, h: SDS(tuple(s.shape), s.dtype, sharding=h), state, sh
                )
                args = (state, ins["batch"])
            elif shape.kind == "prefill":
                step = make_prefill_step(cfg)
                args = (pspecs, ins["tokens"], ins["caches"])
                if "memory" in ins:
                    args = args + (ins["memory"],)
            else:
                step = make_serve_step(cfg)
                args = (pspecs, ins["token"], ins["caches"], ins["index"])

            with mesh:
                t_lower = time.time()
                lowered = jax.jit(step).lower(*args)
                t_compile = time.time()
                compiled = lowered.compile()
                t_done = time.time()

        mem = _mem_analysis(compiled)
        try:
            cost = dict(compiled.cost_analysis() or {})
            cost = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float))}
        except Exception as e:
            cost = {"error": str(e)}
        coll = parse_collectives(compiled.as_text())
        record.update(
            status="ok",
            devices=int(mesh.size),
            lower_s=round(t_compile - t_lower, 2),
            compile_s=round(t_done - t_compile, 2),
            memory_analysis=mem,
            cost_analysis=cost,
            collectives=coll,
        )
    except Exception as e:
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    record["wall_s"] = round(time.time() - t0, 2)
    art.write_text(json.dumps(record, indent=2))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="lower 1- and 2-scan-step variants (cost-model probes)")
    ap.add_argument("--variant", default=None,
                    help="named perf variant (see launch/variants.py)")
    ap.add_argument("--out", default="dryrun_artifacts")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = list_archs() if args.all else [args.arch]
    shapes = [s.name for s in SHAPES] if args.all else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    n_ok = n_skip = n_err = 0
    for a, s, m in cells:
        if args.probe:
            cfg = get_config(a)
            for n in (1, 2):
                rec = run_cell(a, s, m, out_dir, force=args.force,
                               tag=f"__probe{n}", cfg_override=probe_layers(cfg, n))
                print(f"[{rec['status'].upper():5s}] probe{n} {a} {s}")
            continue
        tag = f"__v_{args.variant}" if args.variant else ""
        rec = run_cell(a, s, m, out_dir, force=args.force, tag=tag)
        tagm = "2x16x16" if m else "16x16"
        if rec["status"] == "ok":
            n_ok += 1
            ca = rec["cost_analysis"]
            print(
                f"[OK]   {a:26s} {s:12s} {tagm:8s} "
                f"flops={ca.get('flops', 0):.3e} "
                f"wire={rec['collectives']['total_wire_bytes']:.3e}B "
                f"compile={rec['compile_s']}s"
            )
        elif rec["status"] == "skipped":
            n_skip += 1
            print(f"[SKIP] {a:26s} {s:12s} {tagm:8s} {rec['reason']}")
        else:
            n_err += 1
            print(f"[ERR]  {a:26s} {s:12s} {tagm:8s} {rec['error']}")
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
