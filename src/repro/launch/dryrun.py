"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod / 2x16x16
multi-pod of placeholder host devices), constructs shape-only params/inputs
(ShapeDtypeStruct — nothing is allocated), jits the appropriate step with
explicit shardings, and must succeed through ``.lower().compile()``.  It then
records memory analysis, cost analysis (FLOPs / bytes), and the collective
traffic parsed from the optimized HLO into a JSON results file that
benchmarks/roofline.py and EXPERIMENTS.md consume.

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out dryrun_results.json
"""

import argparse
import functools
import json
import math
import os
import re
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import costmodel
from repro.dist import partition
from repro.launch import mesh as mesh_lib
from repro.launch import steps
from repro.models import model as M
from repro.models import modules as nn
from repro.optim import adamw

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
# bytes-on-the-wire weights per op (result-shape based; all-reduce counts 2x
# for its reduce-scatter + all-gather phases)
COLLECTIVE_WEIGHT = {"all-reduce": 2.0, "all-gather": 1.0,
                     "reduce-scatter": 1.0, "all-to-all": 1.0,
                     "collective-permute": 1.0}
DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred)"
                       r"\[([0-9,]*)\]")


def parse_collectives(hlo_text: str) -> dict[str, float]:
    """Sum result bytes of every collective op in optimized HLO, weighted per
    COLLECTIVE_WEIGHT.  Returns {op_name: bytes, ..., 'total': bytes}."""
    out = {op: 0.0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        # result-side ops look like: %name = TYPE ops-name(...)
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        opname = m.group(2)
        base = opname.rstrip("0123456789.")
        base = base.replace("-start", "").replace("-done", "")
        if base not in COLLECTIVE_OPS:
            continue
        if opname.endswith("-done"):
            continue                      # counted at -start
        result_bytes = 0
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            result_bytes += n * DTYPE_BYTES[dt]
        out[base] += COLLECTIVE_WEIGHT[base] * result_bytes
    out["total"] = sum(out[op] for op in COLLECTIVE_OPS)
    return out


def bytes_per_device(sds_tree, shardings) -> float:
    """Analytic per-device bytes of a (ShapeDtypeStruct, NamedSharding) tree."""
    total = 0.0
    for sds, sh in zip(jax.tree.leaves(sds_tree), jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec"))):
        shard_shape = sh.shard_shape(sds.shape)
        total += math.prod(shard_shape) * jnp.dtype(sds.dtype).itemsize
    return total


def count_params(shapes_tree, cfg) -> tuple[int, int]:
    """(total, active) parameter counts from the shape-only param tree."""
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes_tree)[0]:
        n = math.prod(leaf.shape)
        total += n
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        is_expert = cfg.family == "moe" and "ffn" in keys and "router" not in keys
        active += int(n * cfg.top_k / cfg.n_experts) if is_expert else n
    return total, active


def model_flops(cfg, shape, total_params: int, active_params: int) -> float:
    tokens = shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len)
    n = active_params
    per_token = 6 * n if shape.kind == "train" else 2 * n
    return float(per_token) * tokens


# ================================================================== lowering
def build_cell(cfg, shape, mesh):
    """Returns (jitted_fn, example_args_sds) for the cell's step kind."""
    key = jax.random.PRNGKey(0)
    ptree = M.init_lm_shapes(key, cfg)
    pshard = steps.param_shardings(ptree, mesh)
    pspecs = nn.unwrap(ptree)      # ShapeDtypeStruct tree

    if shape.kind == "train":
        opt_specs = jax.eval_shape(adamw.init_opt_state, pspecs)
        oshard = steps.opt_shardings(pshard, mesh)
        bspecs = steps.batch_sds(cfg, shape)
        bshard = steps.batch_shardings(bspecs, mesh)
        nmb = cfg.force_microbatches or steps.pick_microbatches(cfg, shape, mesh)
        fn = functools.partial(steps.train_step, cfg=cfg,
                               opt_cfg=adamw.OptConfig(),
                               num_microbatches=nmb)
        jfn = jax.jit(fn,
                      in_shardings=(pshard, oshard, bshard),
                      out_shardings=(pshard, oshard, None),
                      donate_argnums=(0, 1))
        return jfn, (pspecs, opt_specs, bspecs), {"num_microbatches": nmb}

    if shape.kind == "prefill":
        bspecs = steps.batch_sds(cfg, shape, with_labels=False)
        bshard = steps.batch_shardings(bspecs, mesh)
        cshard = steps.cache_shardings(cfg, mesh, shape.global_batch,
                                       shape.seq_len)
        fn = functools.partial(steps.prefill_step, cfg=cfg,
                               max_len=shape.seq_len)
        jfn = jax.jit(fn, in_shardings=(pshard, bshard),
                      out_shardings=(None, cshard))
        return jfn, (pspecs, bspecs), {}

    if shape.kind == "decode":
        cspecs = steps.cache_sds(cfg, shape.global_batch, shape.seq_len)
        cshard = steps.cache_shardings(cfg, mesh, shape.global_batch,
                                       shape.seq_len)
        tspecs = steps.decode_tokens_sds(shape.global_batch)
        tshard = partition.named_sharding(("batch",), mesh,
                                          shape=(shape.global_batch,))
        fn = functools.partial(steps.serve_step, cfg=cfg)
        jfn = jax.jit(fn, in_shardings=(pshard, cshard, tshard),
                      out_shardings=(None, cshard), donate_argnums=(1,))
        return jfn, (pspecs, cspecs, tspecs), {}

    raise ValueError(shape.kind)


def probe_cfg(cfg, units: int):
    """A ``units``-deep variant of ``cfg`` for unrolled cost probing, plus the
    full model's unit count (fractional for hybrid trailing layers)."""
    import dataclasses
    if cfg.family == "hybrid":
        return (dataclasses.replace(cfg, n_layers=units * cfg.hybrid_group,
                                    scan_layers=False),
                cfg.n_layers / cfg.hybrid_group)
    if cfg.family == "enc_dec":
        return (dataclasses.replace(cfg, enc_layers=units, dec_layers=units,
                                    n_layers=2 * units, scan_layers=False),
                cfg.enc_layers)
    return dataclasses.replace(cfg, n_layers=units, scan_layers=False), cfg.n_layers


def rules_for(cfg):
    rules = dict(partition.DEFAULT_RULES)
    if cfg.seq_shard:
        rules["seq"] = "model"        # SP: every seq constraint follows
    return rules


def measure_costs(cfg, shape, mesh) -> dict[str, float]:
    """Compile the cell and return {'flops','bytes','coll/<op>',...} per device."""
    with partition.mesh_rules(mesh, rules_for(cfg)):
        jfn, args, _ = build_cell(cfg, shape, mesh)
        compiled = jfn.lower(*args).compile()
    out: dict[str, float] = {}
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    out["flops"] = float(ca.get("flops", 0))
    out["bytes"] = float(ca.get("bytes accessed", 0))
    coll = parse_collectives(compiled.as_text())
    for k, v in coll.items():
        out[f"coll/{k}"] = v
    return out


def extrapolated_costs(cfg, shape, mesh) -> dict[str, Any]:
    """XLA counts loop bodies once, so the scanned artifact under-reports
    per-layer costs by ~n_layers.  Probe the cell UNROLLED at depths 1 and 2
    and extrapolate linearly — exact for homogeneous stacks:
        cost(L) = c1 + (L - 1) * (c2 - c1).
    """
    p1, full_units = probe_cfg(cfg, 1)
    p2, _ = probe_cfg(cfg, 2)
    c1 = measure_costs(p1, shape, mesh)
    c2 = measure_costs(p2, shape, mesh)
    out = {k: c1[k] + (full_units - 1) * (c2[k] - c1[k]) for k in c1}
    out["probe_flops_1"] = c1["flops"]
    out["probe_flops_2"] = c2["flops"]
    out["full_units"] = full_units
    return out


def _apply_overrides(cfg, overrides: dict[str, Any] | None):
    if not overrides:
        return cfg
    import dataclasses
    typed = {}
    for k, v in overrides.items():
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            typed[k] = v in (True, "true", "True", "1")
        elif isinstance(cur, int):
            typed[k] = int(v)
        elif isinstance(cur, float):
            typed[k] = float(v)
        else:
            typed[k] = v
    return dataclasses.replace(cfg, **typed)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             verbose: bool = True,
             overrides: dict[str, Any] | None = None) -> dict[str, Any]:
    cfg = _apply_overrides(configs.get(arch), overrides)
    shape = configs.SHAPES[shape_name]
    ok, reason = configs.applicable(cfg, shape)
    rec: dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "kind": shape.kind}
    if overrides:
        rec["overrides"] = dict(overrides)
    if not ok:
        rec["status"] = "skipped"
        rec["skip_reason"] = reason
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh_lib.chips(mesh)
    # --- 1. the REAL production artifact (scan-over-layers) must compile ----
    with partition.mesh_rules(mesh, rules_for(cfg)):
        t0 = time.time()
        jfn, args, extra = build_cell(cfg, shape, mesh)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes") if hasattr(mem, k)}
        rec["memory_per_device_bytes"] = (
            rec["memory_analysis"].get("argument_size_in_bytes", 0)
            + rec["memory_analysis"].get("temp_size_in_bytes", 0))
    except Exception as e:                      # CPU backend may not support
        rec["memory_analysis"] = f"unavailable: {e}"

    # --- 2. depth-probe cost extrapolation (see extrapolated_costs) ---------
    costs = extrapolated_costs(cfg, shape, mesh)
    rec["flops_per_device"] = costs["flops"]
    rec["hlo_bytes_per_device"] = costs["bytes"]
    coll = {k.split("/", 1)[1]: v for k, v in costs.items()
            if k.startswith("coll/")}
    rec["collective_bytes"] = coll
    rec["probe"] = {k: costs[k] for k in
                    ("probe_flops_1", "probe_flops_2", "full_units")}

    # analytic per-device residency (params + step inputs)
    ptree = M.init_lm_shapes(jax.random.PRNGKey(0), cfg)
    pshard = steps.param_shardings(ptree, mesh)
    rec["param_bytes_per_device"] = bytes_per_device(nn.unwrap(ptree), pshard)
    total_p, active_p = count_params(nn.unwrap(ptree), cfg)
    rec["params_total"] = total_p
    rec["params_active"] = active_p

    # roofline terms (per §Roofline: per-chip rates; HLO numbers are already
    # per device post-SPMD)
    terms = {
        "compute_s": max(rec["flops_per_device"], 0) / costmodel.PEAK_FLOPS_BF16,
        "memory_s": max(rec["hlo_bytes_per_device"], 0) / costmodel.HBM_BW,
        "collective_s": coll["total"] / chips / costmodel.ICI_BW_PER_LINK,
    }
    terms["dominant"] = costmodel.dominant_term(terms)
    rec["roofline"] = terms
    mf = model_flops(cfg, shape, total_p, active_p)
    rec["model_flops_total"] = mf
    hlo_total = max(rec["flops_per_device"], 0) * chips
    rec["useful_flops_ratio"] = (mf / hlo_total) if hlo_total > 0 else None
    rec["chips"] = chips
    rec["lower_s"] = round(t_lower, 2)
    rec["compile_s"] = round(t_compile, 2)
    rec["status"] = "ok"
    rec.update(extra)
    if verbose:
        dom = terms["dominant"]
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: OK "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
              f"dominant={dom} {terms[dom] * 1e3:.2f}ms, "
              f"useful_flops={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)})")
    return rec


# ====================================================================== CLI
def load_results(path: str) -> dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, results: dict[str, Any]) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def cell_key(arch, shape, mesh_kind) -> str:
    return f"{arch}|{shape}|{mesh_kind}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override for §Perf hillclimbs, e.g. "
                         "--override remat_policy=dots (repeatable)")
    ap.add_argument("--tag", default="",
                    help="suffix for the results key (names the experiment)")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.override)

    if args.list:
        for name, _, shape, ok, reason in configs.cells():
            print(f"{name:24s} {shape.name:12s} "
                  f"{'RUN' if ok else 'SKIP: ' + reason}")
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        todo = [(n, s.name) for n, _, s, _, _ in configs.cells()]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]

    results = load_results(args.out)
    for arch, shape in todo:
        for mk in meshes:
            key = cell_key(arch, shape, mk)
            if args.tag:
                key += f"#{args.tag}"
            if not args.force and results.get(key, {}).get("status") in ("ok", "skipped"):
                print(f"[dryrun] {key}: cached, skipping")
                continue
            try:
                rec = run_cell(arch, shape, mk, overrides=overrides)
            except Exception as e:
                import traceback
                rec = {"arch": arch, "shape": shape, "mesh": mk,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"[dryrun] {key}: ERROR {type(e).__name__}: {e}")
            results[key] = rec
            save_results(args.out, results)


if __name__ == "__main__":
    # the production meshes are placeholder host devices; the flag must be in
    # place before the first backend use, and is added to the caller's flags
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))
    main()
