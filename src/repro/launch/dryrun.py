import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds ShapeDtypeStruct stand-ins for params,
optimizer state, batch and caches (no allocation), attaches PartitionSpecs
from ``repro.sharding.partition``, and runs ``jax.jit(...).lower().compile()``
against the production mesh — 16×16 (single pod) and 2×16×16 (2 pods).
It records ``memory_analysis()`` (proves the cell fits HBM),
``cost_analysis()`` (FLOPs/bytes for the roofline) and the collective-op
byte census parsed from the optimized HLO, as one JSON per cell under
``--out`` (default dryrun_results/), so the sweep is resumable.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh pod
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, VERIFY_K, applicable, get_config, input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import TrainState, build_decode_step, build_prefill_step, build_train_step
from repro.models import zoo
from repro.optim import adafactor, adamw
from repro.sharding.partition import Partitioner

V5E_HBM_BYTES = 16 * 1024**3
COLLECTIVE_RE = re.compile(
    r"=\s+(?:\([^)]*\)|((?:[a-z0-9]+)\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\("
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
TUPLE_SHAPE_RE = re.compile(r"=\s+\(([^)]*)\)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_census(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the optimized HLO, by type."""
    stats: dict = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group(2)
        total = 0
        if m.group(1):
            sm = SHAPE_RE.match(m.group(1))
            if sm:
                total = _shape_bytes(sm.group(1), sm.group(2))
        else:
            tm = TUPLE_SHAPE_RE.search(line)
            if tm:
                for sm in SHAPE_RE.finditer(tm.group(1)):
                    total += _shape_bytes(sm.group(1), sm.group(2))
        rec = stats.setdefault(op, {"bytes": 0, "count": 0})
        rec["bytes"] += total
        rec["count"] += 1
    return stats


def _as_cost_dict(cost) -> dict:
    """Older jax returns [dict] from compiled.cost_analysis(), newer a dict."""
    if isinstance(cost, (list, tuple)):
        return cost[0] if cost else {}
    return cost or {}


def _replicated(mesh, tree):
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)


# Probe layer counts per family for scan-body scaling (XLA cost_analysis
# counts a while-loop body once; two probes give the per-layer delta so
# FLOPs/bytes/collectives can be scaled to the real depth).
PROBE_LAYERS = {
    "dense": (1, 2), "moe": (1, 2), "vlm": (1, 2), "audio": (1, 2),
    "hybrid": (3, 6), "ssm": (8, 16),
}


def _with_layers(cfg, n: int):
    kw = dict(n_layers=n)
    if cfg.layer_kinds:
        kw["layer_kinds"] = cfg.layer_kinds[:n]
    if cfg.window_sizes:
        kw["window_sizes"] = cfg.window_sizes[:n]
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=n)
    return dataclasses.replace(cfg, **kw)


def build_cell(arch: str, shape_name: str, mesh, dtype_override: str = "bfloat16", cfg=None):
    """Returns (step_fn, arg_specs, in_shardings, out_shardings, donate)."""
    if cfg is None:
        cfg = get_config(arch)
    if dtype_override:
        cfg = dataclasses.replace(cfg, dtype=dtype_override, param_dtype=dtype_override)
    shape = SHAPES[shape_name]
    part = Partitioner(mesh)

    key = jax.random.PRNGKey(0)
    params_spec = jax.eval_shape(lambda: zoo.init(key, cfg))
    params_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), part.param_specs(params_spec))
    batch_spec = input_specs(cfg, shape, n_tokens=1 if shape.kind == "decode" else None)
    batch_sh = part.batch_shardings(batch_spec)

    if shape.kind == "train":
        opt = adafactor(1e-4) if cfg.param_count() > 5e10 else adamw(1e-4)
        opt_spec = jax.eval_shape(opt.init, params_spec)
        opt_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), part.param_specs(opt_spec))
        state_spec = TrainState(params_spec, opt_spec, jax.ShapeDtypeStruct((), jnp.int32))
        state_sh = TrainState(params_sh, opt_sh, NamedSharding(mesh, P()))
        step = build_train_step(cfg, opt)
        metrics_sh = None  # let the compiler choose (scalars)
        return (
            step,
            (state_spec, batch_spec),
            (state_sh, batch_sh),
            (state_sh, metrics_sh),
            (0,),
            cfg,
            part,
        )

    if shape.kind == "prefill":
        cache_spec = zoo.cache_spec(params_spec, batch_spec, cfg, shape.seq_len)
        cache_sh = part.cache_shardings(cache_spec)
        step = build_prefill_step(cfg)
        return (
            step,
            (params_spec, batch_spec, cache_spec),
            (params_sh, batch_sh, cache_sh),
            (None, cache_sh),
            (2,),
            cfg,
            part,
        )

    # decode: one new token against a seq_len KV cache.
    # The cache is built for a prefill-shaped batch, then the step consumes
    # [B, 1] tokens; max_len has headroom for a draft window.
    proto_batch = {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 8), jnp.int32)}
    if cfg.family == "audio":
        proto_batch["frames"] = jax.ShapeDtypeStruct((shape.global_batch, cfg.encoder.n_ctx, cfg.d_model), jnp.dtype(cfg.dtype))
    cache_spec = zoo.cache_spec(params_spec, proto_batch, cfg, shape.seq_len + 64)
    cache_sh = part.cache_shardings(cache_spec)
    tokens_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tokens_sh = part.batch_shardings(tokens_spec)
    step = build_decode_step(cfg)
    return (
        step,
        (params_spec, tokens_spec, cache_spec),
        (params_sh, tokens_sh, cache_sh),
        (None, cache_sh),
        (2,),
        cfg,
        part,
    )


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path, force: bool = False) -> dict:
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_kind}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "ok": False}
    if skip:
        rec.update(ok=True, skipped=skip)
        out_path.write_text(json.dumps(rec, indent=2))
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        step, arg_specs, in_sh, out_sh, donate, cfg2, part = build_cell(arch, shape_name, mesh)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate)
            lowered = jitted.lower(*arg_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = _as_cost_dict(compiled.cost_analysis())
            hlo = compiled.as_text()
        coll = collective_census(hlo)
        # --- probe compiles: scale scan-body metrics to the real depth ------
        # Probes fully unroll every lax.scan (cost_analysis counts a while
        # body once) so flops/bytes/collectives deltas reflect true per-layer
        # costs; the full compile above provides memory_analysis.
        l1, l2 = PROBE_LAYERS[cfg.family]
        probes = {}
        for lp in (l1, l2):
            pcfg = dataclasses.replace(_with_layers(cfg, lp), scan_unroll=True)
            pstep, pargs, pin, pout, pdon, _, _ = build_cell(arch, shape_name, mesh, cfg=pcfg)
            with jax.set_mesh(mesh):
                pcompiled = jax.jit(pstep, in_shardings=pin, out_shardings=pout, donate_argnums=pdon).lower(*pargs).compile()
                pcost = _as_cost_dict(pcompiled.cost_analysis())
                pcoll = collective_census(pcompiled.as_text())
            probes[lp] = {
                "flops": float(pcost.get("flops", 0.0)),
                "bytes": float(pcost.get("bytes accessed", 0.0)),
                "coll_bytes": sum(v["bytes"] for v in pcoll.values()),
                "coll": pcoll,
            }
        steps_n = (cfg.n_layers - l1) / (l2 - l1)
        flops_scaled = probes[l1]["flops"] + steps_n * (probes[l2]["flops"] - probes[l1]["flops"])
        bytes_scaled = probes[l1]["bytes"] + steps_n * (probes[l2]["bytes"] - probes[l1]["bytes"])
        coll_scaled = probes[l1]["coll_bytes"] + steps_n * (probes[l2]["coll_bytes"] - probes[l1]["coll_bytes"])
        n_dev = mesh.size
        mem_rec = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        }
        per_dev = mem_rec["argument_bytes"] + mem_rec["output_bytes"] + mem_rec["temp_bytes"] - mem_rec["alias_bytes"]
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        rec.update(
            ok=True,
            devices=n_dev,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=mem_rec,
            per_device_bytes=int(per_dev),
            fits_v5e_16g=bool(per_dev <= V5E_HBM_BYTES),
            flops=flops,
            bytes_accessed=bytes_acc,
            collectives={k: v for k, v in sorted(coll.items())},
            collective_bytes=int(sum(v["bytes"] for v in coll.values())),
            flops_scaled=flops_scaled,
            bytes_scaled=bytes_scaled,
            collective_bytes_scaled=int(max(coll_scaled, 0)),
            probes={str(k): {kk: vv for kk, vv in v.items() if kk != "coll"} for k, v in probes.items()},
            sharding_fallbacks=part.fallbacks,
            model_params=cfg.param_count(),
            active_params=cfg.active_param_count(),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(ok=False, error=f"{type(e).__name__}: {e}", traceback=traceback.format_exc()[-4000:])
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="dryrun_results")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_cell(arch, shape, mesh_kind, out_dir, force=args.force)
                status = "SKIP " + rec.get("skipped", "") if rec.get("skipped") else ("OK" if rec["ok"] else "FAIL")
                extra = ""
                if rec.get("ok") and not rec.get("skipped"):
                    extra = (
                        f" per_dev={rec['per_device_bytes']/2**30:.2f}GiB fits={rec['fits_v5e_16g']}"
                        f" flops={rec['flops_scaled']:.3e} coll={rec['collective_bytes']/2**20:.1f}MiB"
                        f" compile={rec['compile_s']}s"
                    )
                if not rec["ok"]:
                    n_fail += 1
                    extra = " " + rec.get("error", "")[:200]
                print(f"[{arch} × {shape} × {mesh_kind}] {status}{extra}", flush=True)
    print(f"\ndry-run complete; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
