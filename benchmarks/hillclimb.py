import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # 512 virtual host devices, never a chip

"""§Perf hillclimbing driver.

Applies one named sharding/layout variant to a single (arch × shape × mesh)
cell, re-derives the roofline terms via the depth-extrapolated cost lowering,
and prints the before/after — one hypothesis→change→measure cycle per run.

Variants:
  baseline    — the paper-faithful 2D layout (recorded already by dryrun)
  act_repl    — residual stream replicated over model (classic Megatron f/g)
  act_seq     — residual stream sequence-sharded over model (Megatron-SP)
  kv_model    — KV projections contract over model-sharded D (psum of small
                [B,S,Kv,Dh] partials instead of gathering x for kv)
  bf16_params — bf16 parameter storage => bf16 gradient reductions
  relic_ring  — act_seq + Relic two-lane ring MLP (fused AG(gate,up) + RS)
  combo       — best measured combination

Usage:
  PYTHONPATH=src python -m benchmarks.hillclimb --arch llama3_405b \
      --shape train_4k --mesh pod --variant act_seq [--top-colls]
"""

import argparse
import json
import re
import time
from pathlib import Path

import jax

from repro import sharding as shd
from repro.configs import SHAPES, get_config

ART = Path(__file__).resolve().parent / "artifacts" / "hillclimb"

KV_MODEL_RULES = [
    (r"(^|/)attn/wk$", ("model", None, None)),
    (r"(^|/)attn/wv$", ("model", None, None)),
]


def apply_variant(cfg, variant: str):
    shd.set_activation_layout("tp")
    shd.set_param_rule_overrides([])
    if variant == "baseline":
        return cfg
    if variant == "act_repl":
        shd.set_activation_layout("replicated")
        return cfg
    if variant == "act_seq":
        shd.set_activation_layout("seq")
        return cfg
    if variant == "kv_model":
        shd.set_param_rule_overrides(KV_MODEL_RULES)
        return cfg
    if variant == "bf16_params":
        return cfg.replace(param_dtype="bfloat16")
    if variant == "relic_ring":
        shd.set_activation_layout("seq")
        return cfg.replace(mlp_tp_overlap=True)
    if variant == "combo":
        shd.set_activation_layout("seq")
        shd.set_param_rule_overrides(KV_MODEL_RULES)
        return cfg.replace(mlp_tp_overlap=True, param_dtype="bfloat16")
    if variant == "attn_big":      # memory-bound: bigger attention tiles
        return cfg.replace(attn_chunk=4096, attn_chunk_q=2048)
    if variant == "cap1":          # MoE: capacity factor 1.0 (smaller buffers)
        import dataclasses
        return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=1.0))
    if variant == "remat_dots":    # save matmul outputs, recompute elementwise
        return cfg.replace(remat="dots")
    if variant == "attn_big_ring":
        shd.set_activation_layout("seq")
        return cfg.replace(attn_chunk=4096, attn_chunk_q=2048,
                           mlp_tp_overlap=True)
    if variant == "mixed":        # bf16 block-input gathers, sharded resid
        shd.set_activation_layout("mixed")
        return cfg
    if variant == "bf16_reduce":  # bf16 cross-shard partial-sum all-reduce
        return cfg.replace(bf16_reduce=True)
    if variant == "mixed_bf16r":
        shd.set_activation_layout("mixed")
        return cfg.replace(bf16_reduce=True)
    if variant == "causal_skip":  # skip fully-masked causal KV blocks
        return cfg.replace(causal_skip=True)
    if variant == "seq_skip":
        shd.set_activation_layout("seq")
        return cfg.replace(causal_skip=True)
    if variant == "repl_skip":
        shd.set_activation_layout("replicated")
        return cfg.replace(causal_skip=True)
    if variant == "cap1_skip":
        import dataclasses
        return cfg.replace(causal_skip=True,
                           moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=1.0))
    if variant == "repl_dots":
        shd.set_activation_layout("replicated")
        return cfg.replace(remat="dots")
    if variant == "seq_dots":
        shd.set_activation_layout("seq")
        return cfg.replace(remat="dots")
    if variant == "fine":          # finer cost tiles (measure tile effects)
        return cfg.replace(attn_chunk_q=256, attn_chunk=2048)
    if variant == "fine_skip":
        return cfg.replace(attn_chunk_q=256, attn_chunk=2048,
                           causal_skip=True)
    raise ValueError(variant)


_COLL_LINE = re.compile(
    r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def top_collectives(hlo: str, n=12):
    from repro.launch.dryrun import _DTYPE_BYTES

    rows = []
    for line in hlo.splitlines():
        m = _COLL_LINE.search(line)
        if not m:
            continue
        dt_, dims, kind = m.groups()
        if dt_ not in _DTYPE_BYTES:
            continue
        size = _DTYPE_BYTES[dt_]
        for d in dims.split(","):
            if d:
                size *= int(d)
        rows.append((size, kind, f"{dt_}[{dims}]"))
    rows.sort(reverse=True)
    agg = {}
    for size, kind, shape in rows:
        key = (kind, shape)
        c, s = agg.get(key, (0, 0))
        agg[key] = (c + 1, s + size)
    top = sorted(agg.items(), key=lambda kv: -kv[1][1])[:n]
    return [
        f"  {kind:<18} {shape:<40} x{c:<4} {s/2**30:8.2f} GiB total"
        for (kind, shape), (c, s) in top
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--top-colls", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.launch import dryrun as dr
    from repro.launch.mesh import make_production_mesh

    ART.mkdir(parents=True, exist_ok=True)
    out = ART / f"{args.arch}__{args.shape}__{args.mesh}__{args.variant}.json"
    if out.exists() and not args.force:
        rec = json.loads(out.read_text())
        print(json.dumps(rec["roofline_terms_s"], indent=2))
        return

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
    cfg = apply_variant(cfg, args.variant)

    t0 = time.time()
    cost = dr._cost_points(cfg, shape, mesh)
    terms = {
        "compute_s": cost["flops"] / dr.PEAK_FLOPS,
        "memory_s": cost["bytes"] / dr.HBM_BW,
        "collective_s": cost["coll"] / dr.ICI_BW,
    }
    rec = {
        "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
        "variant": args.variant,
        "per_device": {"hlo_flops": cost["flops"], "hlo_bytes": cost["bytes"],
                       "collective_wire_bytes": cost["coll"],
                       "collective_by_kind": cost["coll_by_kind"]},
        "roofline_terms_s": terms,
        "dominant": max(terms, key=terms.get),
        "wall_s": round(time.time() - t0, 1),
    }
    out.write_text(json.dumps(rec, indent=2))
    print(f"{args.arch} × {args.shape} × {args.mesh} [{args.variant}]")
    for k, v in terms.items():
        print(f"  {k:>13}: {v:9.4f} s")
    for k, v in cost["coll_by_kind"].items():
        if v:
            print(f"  {k:>20}: {v/2**30:9.1f} GiB")

    if args.top_colls:
        small = dr._prep_cfg(cfg, shape, scan=False, overrides={"n_layers": 2})
        if cfg.family == "hybrid":
            small = small.replace(n_layers=cfg.attn_every or 2)
        if cfg.family == "encdec":
            small = small.replace(enc_layers=2)
        _, comp, _ = dr.lower_cell(small, shape, mesh)
        print("top collectives (2-layer module):")
        for line in top_collectives(comp.as_text()):
            print(line)


if __name__ == "__main__":
    main()
