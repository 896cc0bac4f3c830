import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # 512 virtual host devices, never a chip

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract the roofline terms from the compiled artifact.

Per cell this produces:
  * PROOF lowering — the full config (scanned layers), compiled on the target
    mesh. Success proves the sharding is coherent; `memory_analysis()` gives
    bytes/device.
  * COST lowerings — two small UNROLLED depth variants of the same family
    (XLA's HloCostAnalysis counts a `while` body once, so scanned-depth FLOPs
    must be recovered by exact linear extrapolation: every per-layer term is
    identical, so f(L) = f(L2) + (L-L2) * (f(L3)-f(L2))/(L3-L2); hybrids get
    a group+tail decomposition).
  * Collective byte parse of the partitioned HLO (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute), converted to wire
    bytes with ring-algorithm factors and the op's replica group size.

Results are cached as JSON under benchmarks/artifacts/dryrun/.
"""

import argparse
import json
import math
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.steps import (
    abstract_serve_state,
    abstract_train_state,
    make_serve_step,
    make_train_step,
)
from repro.models.registry import build_model
from repro.optim import OptConfig

ART_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"

# v5e-flavoured hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link

_COLL_RE = re.compile(
    r"=\s+(?:\(?)([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.X)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}


def parse_collectives(hlo_text: str) -> dict:
    """Sum estimated wire bytes per collective kind from partitioned HLO."""
    out = {k: 0.0 for k in ("all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute")}
    counts = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        size = _DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                size *= int(d)
        g = _GROUPS_RE.search(line)
        if g:
            group = int(g.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            group = len(gl.group(1).split(",")) if gl else 2
        # ring-algorithm wire bytes per device (result shape is per-device)
        if kind == "all-gather":
            wire = size * (group - 1) / group
        elif kind == "all-reduce":
            wire = 2 * size * (group - 1) / group
        elif kind == "reduce-scatter":
            wire = size * (group - 1)          # result is the scattered shard
        elif kind == "all-to-all":
            wire = size * (group - 1) / group
        else:  # collective-permute: point-to-point
            wire = size
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


# ---------------------------------------------------------------------------
# Model FLOPs (analytic 6·N·D for train, 2·N·D for a decode token)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> float:
    m = build_model(cfg)
    sds = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    total = sum(np.prod(x.shape) for x in jax.tree.leaves(sds))
    if active_only and cfg.moe is not None:
        mc = cfg.moe
        per_expert = 3 * cfg.d_model * mc.d_ff
        inactive = ((cfg.n_layers - cfg.first_k_dense) * per_expert
                    * (mc.held[1] - mc.top_k))
        total -= inactive
    return float(total)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = count_params(cfg, active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------

def _batch_axes(mesh, b: int):
    axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    prod = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and b % prod == 0:
        return axes
    if "data" in mesh.axis_names and b % mesh.shape["data"] == 0:
        return ("data",)
    return None


def batch_shardings(mesh, batch_sds):
    def one(sds):
        ba = _batch_axes(mesh, sds.shape[0]) if sds.ndim else None
        spec = [None] * sds.ndim
        if sds.ndim and ba:
            spec[0] = ba
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch_sds)


def replicated(mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Lower + compile one configuration
# ---------------------------------------------------------------------------

def _prep_cfg(cfg: ModelConfig, shape: ShapeConfig, *, scan: bool,
              overrides: dict | None = None) -> ModelConfig:
    kw = {"scan_layers": scan}
    if shape.kind == "decode":
        kw["param_dtype"] = "bfloat16"
        kw["remat"] = "none"
    if not scan:
        # COST lowerings statically unroll the chunked-attention scans so
        # HloCostAnalysis counts every block (FLOPs are tiling-invariant);
        # coarser tiles keep the unrolled HLO tractable. Non-default tile
        # settings (hillclimb variants) are preserved.
        if cfg.attn_chunk_q == 512:
            kw.setdefault("attn_chunk_q", 4096)
        if cfg.attn_chunk == 1024:
            kw.setdefault("attn_chunk", 8192)
    if overrides:
        kw.update(overrides)
    return cfg.replace(**kw)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *, compile_: bool = True):
    """Returns (lowered, compiled|None, meta)."""
    model = build_model(cfg)
    batch_sds, cache_len = model.input_specs(shape)
    t0 = time.time()
    with shd.use_sharding_rules(mesh):
        if shape.kind == "decode":
            params_sds, cache_sds = abstract_serve_state(model, shape)
            in_sh = (
                shd.named_shardings(params_sds, mesh),
                shd.named_shardings(cache_sds, mesh),
                batch_shardings(mesh, batch_sds["tokens"]),
                replicated(mesh),
            )
            logits_sds = jax.ShapeDtypeStruct((shape.global_batch, 1),
                                              jnp.float32)
            logits_spec = shd.fit_spec(
                mesh, [_batch_axes(mesh, shape.global_batch), None],
                logits_sds.shape)
            out_sh = (
                batch_shardings(mesh, batch_sds["tokens"]),
                NamedSharding(mesh, logits_spec),
                shd.named_shardings(cache_sds, mesh),
            )
            fn = jax.jit(make_serve_step(model), in_shardings=in_sh,
                         out_shardings=out_sh, donate_argnums=(1,))
            lowered = fn.lower(
                params_sds, cache_sds, batch_sds["tokens"],
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        else:
            state_sds = abstract_train_state(model)
            state_sh = shd.named_shardings(state_sds, mesh)
            in_sh = (state_sh, batch_shardings(mesh, batch_sds))
            metrics_sh = {k: replicated(mesh) for k in
                          ("loss", "ce", "aux", "tokens", "grad_norm", "lr")}
            fn = jax.jit(make_train_step(model, OptConfig()),
                         in_shardings=in_sh,
                         out_shardings=(state_sh, metrics_sh),
                         donate_argnums=(0,))
            lowered = fn.lower(state_sds, batch_sds)
    lower_s = time.time() - t0
    compiled = None
    compile_s = None
    if compile_:
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
    return lowered, compiled, {"lower_s": lower_s, "compile_s": compile_s}


def _cost_points(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Unrolled small-depth lowerings for exact linear-in-depth costs."""
    fam = cfg.family

    def costs(c):
        _, comp, _ = lower_cell(c, shape, mesh)
        ca = comp.cost_analysis()
        coll = parse_collectives(comp.as_text())
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll["total"],
            "coll_by_kind": {k: coll[k] for k in
                             ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")},
        }

    def lin(f2, f3, l2, l3, target):
        per = {k: (f3[k] - f2[k]) / (l3 - l2) for k in ("flops", "bytes", "coll")}
        out = {k: f2[k] + per[k] * (target - l2) for k in per}
        out["coll_by_kind"] = {
            k: f2["coll_by_kind"][k]
            + (f3["coll_by_kind"][k] - f2["coll_by_kind"][k]) / (l3 - l2)
            * (target - l2)
            for k in f2["coll_by_kind"]
        }
        return out

    if fam == "hybrid" and cfg.attn_every:
        ae = cfg.attn_every
        f_g1 = costs(_prep_cfg(cfg, shape, scan=False,
                               overrides={"n_layers": ae}))
        f_g2 = costs(_prep_cfg(cfg, shape, scan=False,
                               overrides={"n_layers": 2 * ae}))
        f_m2 = costs(_prep_cfg(cfg, shape, scan=False,
                               overrides={"n_layers": 2, "attn_every": 0}))
        f_m4 = costs(_prep_cfg(cfg, shape, scan=False,
                               overrides={"n_layers": 4, "attn_every": 0}))
        full, tail = cfg.n_layers // ae, cfg.n_layers % ae
        out = {}
        for k in ("flops", "bytes", "coll"):
            g = f_g2[k] - f_g1[k]                  # one (ae mamba + attn) group
            m = (f_m4[k] - f_m2[k]) / 2.0          # one mamba layer
            out[k] = f_g1[k] + (full - 1) * g + tail * m
        out["coll_by_kind"] = {
            k: f_g1["coll_by_kind"][k]
            + (full - 1) * (f_g2["coll_by_kind"][k] - f_g1["coll_by_kind"][k])
            + tail * (f_m4["coll_by_kind"][k] - f_m2["coll_by_kind"][k]) / 2.0
            for k in f_g1["coll_by_kind"]
        }
        return out

    if fam == "encdec":
        f2 = costs(_prep_cfg(cfg, shape, scan=False,
                             overrides={"n_layers": 2, "enc_layers": 2}))
        f3 = costs(_prep_cfg(cfg, shape, scan=False,
                             overrides={"n_layers": 3, "enc_layers": 3}))
        return lin(f2, f3, 2, 3, cfg.n_layers)

    f2 = costs(_prep_cfg(cfg, shape, scan=False, overrides={"n_layers": 2}))
    f3 = costs(_prep_cfg(cfg, shape, scan=False, overrides={"n_layers": 3}))
    return lin(f2, f3, 2, 3, cfg.n_layers)


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             force: bool = False) -> dict:
    ART_DIR.mkdir(parents=True, exist_ok=True)
    out_path = ART_DIR / f"{arch}__{shape_name}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": why}
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    n_chips = int(np.prod(list(mesh.shape.values())))

    # PROOF: full depth, scanned, compiled.
    proof_cfg = _prep_cfg(cfg, shape, scan=True)
    _, compiled, meta = lower_cell(proof_cfg, shape, mesh)
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes),
    }

    # COST: extrapolated exact depth costs (per-device).
    cost = _cost_points(cfg, shape, mesh)

    mf = model_flops(cfg, shape)
    flops_dev = cost["flops"]
    bytes_dev = cost["bytes"]
    coll_dev = cost["coll"]
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_dev / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_chips": n_chips,
        "lower_s": round(meta["lower_s"], 2),
        "compile_s": round(meta["compile_s"], 2),
        "memory": mem,
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_wire_bytes": coll_dev,
            "collective_by_kind": cost["coll_by_kind"],
        },
        "model_flops_global": mf,
        "useful_flops_ratio": mf / (flops_dev * n_chips) if flops_dev else None,
        "roofline_terms_s": terms,
        "dominant": dominant,
    }
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [a for a in ARCH_IDS if a != "relic_tiny"] \
        if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{arch} × {shape} × {mesh_name}"
                try:
                    t0 = time.time()
                    rec = run_cell(arch, shape, mesh_name, force=args.force)
                    if "skipped" in rec:
                        print(f"[skip] {tag}: {rec['skipped']}", flush=True)
                    else:
                        t = rec["roofline_terms_s"]
                        print(
                            f"[ok]   {tag}: dom={rec['dominant']} "
                            f"comp={t['compute_s']:.4f}s mem={t['memory_s']:.4f}s "
                            f"coll={t['collective_s']:.4f}s "
                            f"({time.time()-t0:.0f}s wall)", flush=True,
                        )
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nAll requested dry-run cells passed.")


if __name__ == "__main__":
    main()
