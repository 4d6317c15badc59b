"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

MUST set the placeholder device count before any jax import (jax locks the
device count on first init) — hence the first two lines.
"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from ..api.protocol import build_protocol                    # noqa: E402
from ..configs import ARCH_NAMES, SHAPES, get_config        # noqa: E402
from ..dist import use_sharding                              # noqa: E402
from ..dist.amb import AMBConfig                             # noqa: E402
from ..dist.params import tree_shardings                     # noqa: E402
from ..models import decode_step, prefill                    # noqa: E402
from ..optim import DualAveragingOpt                         # noqa: E402
from . import specs as S                                     # noqa: E402
from .mesh import make_mesh, make_production_mesh           # noqa: E402

# v5e constants for §Roofline
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

_COLLECTIVE_RE = re.compile(
    r"=\s+(\S+?)\[([\d,]*)\]\S*\s+(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)\b")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}

# per-chip traffic multipliers (ring algorithms); shapes in the partitioned
# module are per-device.
_TRAFFIC_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                   "reduce-scatter": 1.0, "all-to-all": 1.0,
                   "collective-permute": 1.0}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-op result bytes for every collective in the (partitioned) HLO.

    Each op also carries a ``by_dtype`` byte breakdown — how the quantized
    wire shows up as u8 (vs fp32 / RNG-u32) in the collective-permutes.
    """
    out = {k: {"count": 0, "bytes": 0.0, "by_dtype": {}}
           for k in _TRAFFIC_FACTOR}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dt, 4)
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        out[op]["count"] += 1
        out[op]["bytes"] += size * nbytes
        out[op]["by_dtype"][dt] = out[op]["by_dtype"].get(dt, 0) \
            + size * nbytes
    out["traffic_bytes"] = sum(
        v["bytes"] * _TRAFFIC_FACTOR[k]
        for k, v in out.items() if k in _TRAFFIC_FACTOR)
    return out


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) training; 2*N*D for fwd-only."""
    n_params = cfg.param_count()
    if cfg.is_moe:
        d, ff, e, k = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.experts_per_token
        moe_total = cfg.num_layers * e * 3 * d * ff
        moe_active = cfg.num_layers * k * 3 * d * ff
        n_params = n_params - moe_total + moe_active
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_params * tokens


def _lower_combo(cfg, shape, mesh):
    """Lower the right step for (cfg, shape) on mesh. Returns Lowered."""
    params_sds = S.abstract_params(cfg)
    # Decode serves one token per step: FSDP ("data"-sharded) weights would
    # be re-all-gathered on every matvec (measured: 5 weight gathers/layer
    # on rwkv6 long_500k — §Perf hillclimb 2).  Serving replicates weights
    # over "data" (throughput axis) and keeps tensor parallel on "model".
    # NOTE (§Perf hillclimb 2, iteration 2, REFUTED): replicate_tmix=True
    # for ssm decode cut the collective term 23x (no head-boundary state
    # gathers) but raised the memory term 5.2x (full tmix weights read per
    # token) — the ICI->HBM trade loses: the binding term went 1.8 ms ->
    # 8.6 ms.  Keep tensor-parallel tmix.
    # MoE keeps FSDP at decode too: expert weights dominate its bytes and
    # replicating them over "data" costs ~16x HBM reads per token, which
    # outweighs the dense-layer weight-gather saving (§Perf sweep).
    fsdp = "data" if (shape.kind == "train" or cfg.is_moe) else None
    pspecs = tree_shardings(params_sds, mesh, fsdp_axis=fsdp)
    as_in = lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                                 sharding=sh)
    params_in = jax.tree.map(as_in, params_sds, pspecs)

    if shape.kind == "train":
        from jax.sharding import NamedSharding, PartitionSpec as P
        opt = DualAveragingOpt()
        proto = build_protocol(cfg, mesh, AMBConfig(), optimizer=opt)
        # TrainState structure comes from the protocol itself; only the
        # shardings are assigned here (params keep the fsdp choice above)
        state_sds = jax.eval_shape(proto.init, params_sds)
        state_specs = {"params": pspecs,
                       "opt": tree_shardings(state_sds["opt"], mesh),
                       "t": NamedSharding(mesh, P())}
        state_in = jax.tree.map(as_in, state_sds, state_specs)
        batch = S.train_input_specs(cfg, shape, mesh)
        b = S.worker_batch_spec(mesh)
        return jax.jit(proto.step).lower(state_in, batch, b)
    if shape.kind == "prefill":
        batch = S.prefill_input_specs(cfg, shape, mesh)
        return jax.jit(lambda p, bt: prefill(p, cfg, bt)).lower(
            params_in, batch)
    # decode
    state_sds = S.abstract_decode_state(cfg, shape)
    sspecs = S.decode_state_specs(state_sds, mesh, shape.global_batch)
    state_in = jax.tree.map(
        lambda sds, sp: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=jax.sharding.NamedSharding(mesh, sp)),
        state_sds, sspecs)
    tok = S.decode_token_spec(shape, mesh)
    return jax.jit(lambda p, st, t: decode_step(p, cfg, st, t)).lower(
        params_in, state_in, tok)


def _costs(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": parse_collectives(compiled.as_text())}


def _depth_variant(cfg, layers: int, seq_len: int):
    """Cost-measurement config: reduced depth (encoder scaled in lockstep).

    Chunk sizes stay production-representative (so HBM traffic matches the
    real flash/SSD programs) but are raised at very long sequences to bound
    the unrolled block count — every block body appears explicitly in HLO
    under ``unrolled_loops()``, which is what makes cost_analysis exact."""
    kw = {"num_layers": layers}
    if seq_len > 8192:
        kw["q_chunk"] = kw["kv_chunk"] = 4096
        kw["ssm_chunk"] = 2048
    if cfg.encoder_layers:
        kw["encoder_layers"] = max(
            1, round(cfg.encoder_layers * layers / cfg.num_layers))
    return dataclasses.replace(cfg, **kw)


def extrapolated_costs(cfg, shape, mesh) -> dict:
    """XLA cost_analysis counts while-loop (lax.scan) bodies ONCE, so the
    layer-stack contribution must be recovered by depth extrapolation:
    compile UNROLLED depth p and 2p (p = the repeating unit, attn_every for
    hybrids), then total(L) = c(p) + (L-p)/p * (c(2p) - c(p)).  Exact for
    homogeneous scanned stacks.
    """
    from ..models.common import unrolled_loops
    p = cfg.attn_every if (cfg.family == "hybrid" and cfg.attn_every) else 1
    with unrolled_loops():
        c1 = _costs(_lower_combo(
            _depth_variant(cfg, p, shape.seq_len), shape, mesh).compile())
        c2 = _costs(_lower_combo(
            _depth_variant(cfg, 2 * p, shape.seq_len), shape, mesh).compile())
    k = (cfg.num_layers - p) / p
    out = {
        "flops": c1["flops"] + k * (c2["flops"] - c1["flops"]),
        "bytes": c1["bytes"] + k * (c2["bytes"] - c1["bytes"]),
    }
    coll = {}
    for op in _TRAFFIC_FACTOR:
        b1 = c1["collectives"][op]["bytes"]
        b2 = c2["collectives"][op]["bytes"]
        n1 = c1["collectives"][op]["count"]
        n2 = c2["collectives"][op]["count"]
        coll[op] = {"bytes": b1 + k * (b2 - b1),
                    "count": round(n1 + k * (n2 - n1), 1)}
    coll["traffic_bytes"] = sum(
        coll[op]["bytes"] * _TRAFFIC_FACTOR[op] for op in _TRAFFIC_FACTOR)
    out["collectives"] = coll
    return out


def _mesh(multi_pod: bool):
    """Production mesh, or a reduced test mesh via REPRO_DRYRUN_MESH=d,m."""
    override = os.environ.get("REPRO_DRYRUN_MESH")
    if override:
        dims = tuple(int(x) for x in override.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        return make_mesh(dims, axes)
    return make_production_mesh(multi_pod=multi_pod)


def run_one(arch: str, shape_name: str, multi_pod: bool, outdir: Path,
            consensus: str = "exact") -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch, shape=shape_name)
    mesh = _mesh(multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           "kind": shape.kind, "consensus": consensus}

    t0 = time.time()
    with use_sharding(mesh):
        lowered = _lower_combo(cfg, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        full = _costs(compiled)
        rec["hlo_flops_module"] = full["flops"]
        rec["hlo_bytes_module"] = full["bytes"]
        rec["collectives_module"] = full["collectives"]
        try:
            ma = compiled.memory_analysis()
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                if hasattr(ma, f):
                    rec[f] = int(getattr(ma, f))
        except Exception as e:  # pragma: no cover
            rec["memory_analysis_error"] = str(e)

        # Depth extrapolation (2 extra unrolled compiles) feeds the
        # single-pod §Roofline table; the multi-pod pass only needs the
        # lower+compile proof + memory analysis, so skip it there.
        extr = {} if multi_pod else extrapolated_costs(cfg, shape, mesh)

    rec["hlo_flops"] = extr.get("flops", full["flops"])
    rec["hlo_bytes"] = extr.get("bytes", full["bytes"])
    rec["collectives"] = extr.get("collectives", full["collectives"])
    rec["depth_extrapolated"] = bool(extr)

    # ---- roofline terms (per chip; post-SPMD HLO is per-device) ----
    flops = rec["hlo_flops"]
    rec["model_flops"] = model_flops(cfg, shape)
    rec["compute_s_roofline"] = flops / PEAK_FLOPS
    rec["memory_s_roofline"] = rec["hlo_bytes"] / HBM_BW
    rec["collective_s_roofline"] = (
        rec["collectives"]["traffic_bytes"] / LINK_BW)
    terms = {"compute": rec["compute_s_roofline"],
             "memory": rec["memory_s_roofline"],
             "collective": rec["collective_s_roofline"]}
    rec["dominant_term"] = max(terms, key=terms.get)
    rec["useful_flops_frac"] = (
        rec["model_flops"] / (flops * chips) if flops else 0.0)

    outdir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{rec['mesh']}.json"
    (outdir / name).write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=list(SHAPES) + ["all"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    outdir = Path(args.out)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                path = outdir / f"{arch}__{shape}__{mesh_name}.json"
                if args.skip_existing and path.exists():
                    print(f"[skip] {arch} {shape} {mesh_name}")
                    continue
                t0 = time.time()
                try:
                    rec = run_one(arch, shape, mp, outdir)
                    print(f"[ok]   {arch:22s} {shape:12s} {mesh_name:8s} "
                          f"flops={rec['hlo_flops']:.3e} "
                          f"dom={rec['dominant_term']:10s} "
                          f"({time.time()-t0:.0f}s)")
                except Exception as e:
                    failures.append((arch, shape, mesh_name, str(e)))
                    print(f"[FAIL] {arch} {shape} {mesh_name}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
