import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks device
# count on first init). Placeholder host devices exist ONLY for the dry-run.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Multi-pod dry-run: .lower().compile() every (arch x shape) cell on the
# production meshes, emit memory/cost/collective analysis for §Roofline.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
#       --shape train_4k --mesh single --out artifacts/q3_train.json

import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import RunConfig, SHAPES, get_config, valid_cells
from repro.dist import sharding as sh
from repro.launch import steps as st
from repro.launch.mesh import make_production_mesh
from repro.models import api

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
LINK_BW = 50e9            # bytes/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# ring-cost multipliers applied to the op's result bytes ((n-1)/n ~= 1)
_RING_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _bytes_of_shapes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Sum result-operand bytes of every collective op in optimized HLO."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            # match "= <shape> kind(" including "-start" variants
            if (f" {kind}(" in stripped or f" {kind}-start(" in stripped) \
                    and "=" in stripped:
                lhs = stripped.split(f" {kind}")[0]
                nbytes = _bytes_of_shapes(lhs.split("=", 1)[-1])
                stats[kind]["count"] += 1
                stats[kind]["bytes"] += nbytes
                break
    total = sum(v["bytes"] * _RING_FACTOR[k] for k, v in stats.items())
    stats["weighted_total_bytes"] = int(total)
    return stats


def _spec_sharding(mesh, axes_tree, specs_tree, rules):
    return sh.tree_shardings(mesh, axes_tree, rules, specs_tree)


def lower_cell(arch: str, shape_name: str, mesh, rules=sh.MEGATRON_RULES,
               run: Optional[RunConfig] = None, donate: bool = True,
               cfg=None):
    """Build + lower one (arch x shape) cell on `mesh`. Returns (lowered, meta)."""
    cfg = cfg if cfg is not None else get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train" and cfg.remat == "none":
        # activation checkpointing is mandatory at these shapes (temp memory
        # otherwise exceeds HBM by >10x); probes inherit the same policy
        cfg = cfg.with_(remat="full")
    run = run or RunConfig()
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    with sh.use_sharding(mesh, rules):
        if shape.kind in ("train",):
            step, _ = st.make_train_step(cfg, run, mesh, rules)
            state_specs = st.train_state_specs(cfg, run)
            state_sh = st.train_state_shardings(mesh, cfg, run, rules)
            b_sh, b_specs = st.batch_shardings(mesh, cfg, shape, rules)
            fn = jax.jit(step,
                         in_shardings=(state_sh, b_sh),
                         out_shardings=(state_sh,
                                        {"loss": repl, "grad_norm": repl,
                                         "step": repl}),
                         donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state_specs, b_specs)
        elif shape.kind == "prefill":
            step = st.make_prefill_step(cfg)
            p_specs = api.param_shapes(cfg)
            p_sh = sh.tree_shardings(mesh, api.param_axes(cfg), rules, p_specs)
            b_sh, b_specs = st.batch_shardings(mesh, cfg, shape, rules)
            fn = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = fn.lower(p_specs, b_specs)
        else:  # decode / long_decode
            step = st.make_serve_step(cfg)
            p_specs = api.param_shapes(cfg)
            p_sh = sh.tree_shardings(mesh, api.param_axes(cfg),
                                     rules, p_specs)
            s_specs, s_axes = api.decode_state_specs(cfg, shape.global_batch,
                                                     shape.seq_len)
            s_sh = sh.tree_shardings(mesh, s_axes, rules, s_specs)
            tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            idx = jax.ShapeDtypeStruct((), jnp.int32)
            tok_sh = sh.named_sharding(mesh, ("batch",), rules, tok.shape)
            fn = jax.jit(step,
                         in_shardings=(p_sh, s_sh, tok_sh, repl),
                         donate_argnums=(1,) if donate else ())
            lowered = fn.lower(p_specs, s_specs, tok, idx)
    return lowered, {"cfg": cfg, "shape": shape}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D (dense) / 6*N_active*D, D = tokens processed."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def _cost_of(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    coll = collective_stats(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["weighted_total_bytes"]),
    }


def _probe_cost(arch, shape_name, mesh, rules, run, cfg_variant):
    lowered, _ = lower_cell(arch, shape_name, mesh, rules, run, donate=False,
                            cfg=cfg_variant)
    return _cost_of(lowered.compile())


def probe_corrected_cost(arch: str, shape_name: str, mesh, rules,
                         run: RunConfig, remat: str = "none") -> Dict[str, Any]:
    """XLA cost_analysis counts while(=scan) bodies ONCE. Measure per-layer
    body cost with small UNROLLED probe compiles and reconstruct the true
    total: total = overhead + sum_i trip_i * body_i.
    """
    cfg = get_config(arch).with_(remat=remat) if remat != "none" \
        else get_config(arch)
    u = lambda **kw: cfg.with_(unroll_layers=True, **kw)  # noqa: E731
    out: Dict[str, Any] = {"probes": 0}

    def lin(c0, c1):  # body = c1 - c0 per key
        return {k: c1[k] - c0[k] for k in c0}

    if cfg.family == "hybrid":
        e = cfg.shared_attn_every
        n_groups = cfg.n_layers // e
        rem = cfg.n_layers - n_groups * e
        c_g1 = _probe_cost(arch, shape_name, mesh, rules, run, u(n_layers=e))
        c_g2 = _probe_cost(arch, shape_name, mesh, rules, run,
                           u(n_layers=2 * e))
        body_g = lin(c_g1, c_g2)
        overhead = lin(body_g, c_g1)
        if rem:
            c_t = _probe_cost(arch, shape_name, mesh, rules, run,
                              u(n_layers=e + 1))
            body_m = lin(c_g1, c_t)
        else:
            body_m = {k: 0.0 for k in c_g1}
        total = {k: overhead[k] + n_groups * body_g[k] + rem * body_m[k]
                 for k in c_g1}
        out["probes"] = 3 if rem else 2
    else:
        fkd = cfg.first_k_dense
        s_full = cfg.n_layers - fkd
        c1 = _probe_cost(arch, shape_name, mesh, rules, run,
                         u(n_layers=1, first_k_dense=0))
        c2 = _probe_cost(arch, shape_name, mesh, rules, run,
                         u(n_layers=2, first_k_dense=0))
        body_s = lin(c1, c2)
        overhead = lin(body_s, c1)
        if fkd:
            cd = _probe_cost(arch, shape_name, mesh, rules, run,
                             u(n_layers=2, first_k_dense=1))
            body_d = lin(c2, cd)
            out["probes"] = 3
        else:
            body_d = {k: 0.0 for k in c1}
            out["probes"] = 2
        total = {k: overhead[k] + fkd * body_d[k] + s_full * body_s[k]
                 for k in c1}
    out["corrected"] = total
    return out


_RULESETS = {"megatron": sh.MEGATRON_RULES, "decode": sh.DECODE_RULES,
             "ep": sh.EP_RULES, "dp": sh.DP_RULES, "dpep": sh.DPEP_RULES,
             "fsdp": sh.FSDP_RULES}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_name: str = "megatron", donate: bool = True,
             zero1: bool = True, probes: bool = True,
             master_weights: bool = False,
             remat: str = "none", microbatch: int = 0,
             kv_quant: bool = False) -> Dict[str, Any]:
    rules = _RULESETS[rules_name]
    shape = SHAPES[shape_name]
    if shape.is_decode and rules_name == "megatron":
        rules = sh.DECODE_RULES
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    run = RunConfig(zero1=zero1, master_weights=master_weights,
                    microbatch=microbatch)
    cfg_override = None
    if remat != "none" or kv_quant:
        cfg_override = get_config(arch).with_(
            **({"remat": remat} if remat != "none" else {}),
            **({"kv_quant": True} if kv_quant else {}))
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "rules": rules_name, "chips": int(n_chips), "ok": False,
        "master_weights": master_weights, "remat": remat,
    }
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, rules, run, donate,
                               cfg=cfg_override)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ca = compiled.cost_analysis() or {}
    rec["flops_per_device"] = float(ca.get("flops", 0.0))
    rec["bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes":
                int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    except Exception as e:  # CPU backend may not implement it
        rec["memory"] = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    rec["collectives"] = coll
    rec["hlo_ops"] = {
        "fusion": hlo.count(" fusion("),
        "while": hlo.count(" while("),
    }

    cfg, shp = meta["cfg"], meta["shape"]
    mf = model_flops(cfg, shp)
    rec["model_flops_total"] = mf
    rec["model_flops_per_device"] = mf / n_chips

    # scan-corrected costs (XLA costs while bodies once) via unrolled probes
    flops_dev = rec["flops_per_device"]
    bytes_dev = rec["bytes_per_device"]
    coll_dev = float(coll["weighted_total_bytes"])
    if probes:
        try:
            pc = probe_corrected_cost(
                arch, shape_name, mesh,
                rules if not shape.is_decode else sh.DECODE_RULES,
                run, remat=remat)
            rec["probe"] = pc
            flops_dev = pc["corrected"]["flops"]
            bytes_dev = pc["corrected"]["bytes"]
            coll_dev = pc["corrected"]["coll_bytes"]
        except Exception as e:
            rec["probe"] = {"error": repr(e)[:500]}
    rec["flops_per_device_corrected"] = flops_dev
    rec["bytes_per_device_corrected"] = bytes_dev
    rec["collective_bytes_corrected"] = coll_dev
    rec["useful_flops_ratio"] = (mf / n_chips) / flops_dev if flops_dev else 0.0

    rec["roofline"] = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_dev / LINK_BW,
    }
    terms = rec["roofline"]
    rec["bottleneck"] = max(terms, key=terms.get)
    rec["ok"] = True
    return rec


def main(argv=None) -> None:
    from repro.launch import cli
    ap = cli.make_parser("repro.launch.dryrun",
                         "AOT lower/compile (arch x shape) cells on the "
                         "production meshes")
    cli.add_arch_arg(ap, required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--rules", default="megatron")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--master-weights", action="store_true")
    ap.add_argument("--remat", default="none",
                    choices=("none", "full", "dots"))
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if SHAPES[args.shape] not in valid_cells(cfg):
        rec = {"arch": args.arch, "shape": args.shape, "ok": False,
               "skipped": True,
               "reason": "cell skipped per DESIGN.md §4 (arch-applicability)"}
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as f:
                json.dump([rec], f, indent=1)
        return

    recs = []
    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    for multi in meshes[args.mesh]:
        # roofline probes only on the single-pod mesh (per spec the roofline
        # table is single-pod; the multi-pod pass proves shardability)
        rec = run_cell(args.arch, args.shape, multi, args.rules,
                       zero1=not args.no_zero1, probes=not multi,
                       master_weights=args.master_weights, remat=args.remat,
                       microbatch=args.microbatch, kv_quant=args.kv_quant)
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "ok", "compile_s",
                           "flops_per_device", "bottleneck")}))
        recs.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)


if __name__ == "__main__":
    main()
