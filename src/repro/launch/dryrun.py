"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE FIRST TWO LINES must run before any jax import: they give the CPU host
512 placeholder devices so jax.make_mesh can build the production meshes.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import sharding as shard_lib
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.config import SHAPES
from repro.optim import AdamWConfig
from repro.train.step import make_train_step

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
                "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (post-SPMD,
    per-device) HLO. Returns {op: {count, bytes}}."""
    out = {op: {"count": 0, "bytes": 0} for op in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in COLLECTIVES:
            if f" {op}(" not in stripped and f"{op}-start(" not in stripped:
                continue
            # result shapes live between '=' and the op name
            head = stripped.split(f" {op}", 1)[0]
            if "=" not in head:
                continue
            result = head.split("=", 1)[1]
            nbytes = 0
            for dt, dims in _SHAPE_RE.findall(result):
                if dt not in _DTYPE_BYTES:
                    continue
                size = 1
                for d in dims.split(","):
                    if d:
                        size *= int(d)
                nbytes += size * _DTYPE_BYTES[dt]
            out[op]["count"] += 1
            out[op]["bytes"] += nbytes
            break
    return out


def _linear_costs(meas: dict) -> dict:
    """Scan-aware cost reconstruction.

    XLA's cost_analysis counts a while body ONCE regardless of trip count
    (verified experimentally), so the full-depth compile under-reports
    anything inside the layer scan. We compile L=0 and L=1 variants — both
    count the per-layer body exactly once (L=1 scans are inlined; L=0 runs
    nothing) — giving:

        body  = report(L=1) - report(L=0)
        total = report(L=0) + L * body

    FLOPs / bytes / collective-bytes totals are microbatch-invariant (a
    micro split only re-chunks the same token work; the gradient all-reduce
    and optimizer run once either way), so the L-variants use micro=1.
    """
    out = {}
    a0, a1 = meas["A0"], meas["A1"]
    l_full = meas["L"]
    for key in ("flops", "bytes_accessed", "collective_bytes"):
        body = a1[key] - a0[key]
        out[key] = a0[key] + l_full * body
        out[f"{key}_body"] = body
        out[f"{key}_outer"] = a0[key]
    coll = {}
    for op in COLLECTIVES:
        b0 = a0["collectives"][op]["bytes"]
        b1 = a1["collectives"][op]["bytes"]
        body = b1 - b0
        coll[op] = {"bytes": b0 + l_full * body,
                    "count_once": a1["collectives"][op]["count"]}
    out["collectives_total"] = coll
    return out


def _tok_micro(cfg, shape, mesh) -> int:
    """Gradient-accumulation heuristic: ~8k tokens per device per microbatch."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    per_dev_tokens = shape.global_batch * shape.seq_len // dp
    micro = max(per_dev_tokens // 8192, 1)
    while shape.global_batch % (micro * dp) and micro > 1:
        micro -= 1
    return micro


def _lower_variant(cfg, shape, mesh, micro: int):
    """Lower one program variant. Returns the jax Lowered object."""
    from repro.launch.mesh import batch_axes
    if cfg.shard_attn:
        model_lib.set_attention_sharding(batch_axes(mesh), "model")
    else:
        model_lib.set_attention_sharding((), None)
    params_shape = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = shard_lib.param_specs(params_shape, mesh,
                                   embed_d_shard=cfg.embed_d_shard)
    batch = configs.input_specs(cfg, shape)
    bspecs = shard_lib.batch_specs(cfg, shape, mesh)

    if shape.kind == "train":
        step = make_train_step(cfg, AdamWConfig(), num_microbatches=micro)
        state_shape = {
            "params": params_shape,
            "opt": {"m": params_shape, "v": params_shape,
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}}
        sspecs = shard_lib.state_specs(state_shape, mesh,
                                       embed_d_shard=cfg.embed_d_shard)
        metrics_shape = jax.eval_shape(step, state_shape, batch)[1]
        mspecs = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), metrics_shape)
        with mesh:
            return jax.jit(step, in_shardings=(sspecs, bspecs),
                           out_shardings=(sspecs, mspecs),
                           donate_argnums=(0,)).lower(state_shape, batch)
    cache_shape = configs.cache_specs(cfg, shape)
    cspecs = shard_lib.cache_sharding(cfg, shape, mesh, cache_shape)
    lspec = shard_lib.logits_spec(cfg, shape, mesh)
    if shape.kind == "prefill":
        def fn(params, batch, cache):
            return model_lib.prefill(params, cfg, batch, cache)
        with mesh:
            return jax.jit(fn, in_shardings=(pspecs, bspecs, cspecs),
                           out_shardings=(lspec, cspecs),
                           donate_argnums=(2,)).lower(
                params_shape, batch, cache_shape)

    def fn(params, tokens, cache):
        return model_lib.decode_step(params, cfg, tokens, cache)
    with mesh:
        return jax.jit(fn, in_shardings=(pspecs, bspecs["tokens"], cspecs),
                       out_shardings=(lspec, cspecs),
                       donate_argnums=(2,)).lower(
            params_shape, batch["tokens"], cache_shape)


def _shrink(cfg, layers: int):
    """Depth-k variant for the linear cost reconstruction."""
    import dataclasses
    kw = {"num_layers": layers}
    if cfg.is_encdec:
        kw["encoder_layers"] = layers
    return dataclasses.replace(cfg, **kw)


def _peak_bytes(mem) -> int:
    """Peak live bytes. jax 0.4.x's CompiledMemoryStats has no peak stat;
    the arg+output+temp sum is the standard conservative upper bound."""
    peak = int(getattr(mem, "peak_memory_in_bytes", 0) or 0)
    if peak <= 0:
        peak = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes)
    return peak


def _cost_of(lowered) -> dict:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # jax 0.4.x: one dict per program
        cost = cost[0] if cost else {}
    coll = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collectives": coll,
            "collective_bytes": sum(v["bytes"] for v in coll.values()),
            "compiled": compiled}


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               cfg_override: dict | None = None):
    """Build + lower + compile one cell (full depth for memory analysis,
    L=0/L=1 variants for scan-aware cost reconstruction)."""
    import dataclasses
    cfg = configs.get(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = SHAPES[shape_name]

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return {"status": "skipped",
                "reason": "pure full-attention arch: 512k dense attention "
                          "is out of scope (DESIGN.md §Arch-applicability)"}

    micro = _tok_micro(cfg, shape, mesh) if shape.kind == "train" else 1

    t0 = time.perf_counter()
    full = _cost_of(_lower_variant(cfg, shape, mesh, micro))
    t_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    a0 = _cost_of(_lower_variant(_shrink(cfg, 0), shape, mesh, 1))
    a1 = _cost_of(_lower_variant(_shrink(cfg, 1), shape, mesh, 1))
    meas = {"A0": a0, "A1": a1, "L": cfg.num_layers}
    t_variants = time.perf_counter() - t0
    lin = _linear_costs(meas)

    compiled = full["compiled"]
    mem = compiled.memory_analysis()
    result = {
        "status": "ok",
        "mesh": mesh_name,
        "devices": int(mesh.size),
        "kind": shape.kind,
        # scan-aware reconstructed totals (per device)
        "flops": lin["flops"],
        "bytes_accessed": lin["bytes_accessed"],
        "collective_bytes": lin["collective_bytes"],
        "collectives": lin["collectives_total"],
        "flops_body": lin["flops_body"],
        # raw single-pass report (diagnostic)
        "flops_hlo_once": full["flops"],
        "collectives_hlo_once": full["collectives"],
        # memory proof-of-fit (full-depth program, per device)
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_bytes": _peak_bytes(mem),
        "compile_s": round(t_full, 2),
        "variants_s": round(t_variants, 2),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "num_microbatches": micro,
    }
    return result


# -- the paper's engine on the production mesh -------------------------------
def lower_graph_cell(mesh, mesh_name: str, n: int = 2_000_000,
                     block_size: int = 4096, e_cap: int = 65536,
                     width_per_dev: int = 1):
    """Dry-run the distributed structure-aware sweep (hot path) at pod scale:
    vertex state replicated, blocks round-robin on the data axis, psum/pmax
    reconciliation — storage passed as abstract args (no allocation)."""

    from repro.core.algorithms import pagerank
    from repro.core.engine import _combine_local

    # the dry run must lower the SAME combine op the engine runs (the
    # shared segmented-sum helper), not a hand-rolled twin of it
    pr_prog = pagerank()

    num_blocks = n // block_size
    ndev = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            ndev *= mesh.shape[a]
    width = ndev * width_per_dev

    def device_run(values, psd, src, dstl, w, valid, gids, rows, ok):
        values_in, psd_in = values, psd

        def body(i, carry):
            values, psd = carry
            row = rows[i]
            e_src = src[row]
            msg = values[e_src] * w[row]
            msg = jnp.where(valid[row], msg, 0.0)
            agg = _combine_local(pr_prog, msg, dstl[row], block_size,
                                 use_pallas=False)
            base = gids[row] * block_size
            old = jax.lax.dynamic_slice(values, (base,), (block_size,))
            new = 0.15 / n + 0.85 * agg
            values = jax.lax.dynamic_update_slice(
                values, jnp.where(ok[i], new, old), (base,))
            delta = jnp.abs(new - old).sum() / block_size
            psd = jnp.where(ok[i], psd.at[gids[row]].set(delta), psd)
            return values, psd

        values_l, psd_l = jax.lax.fori_loop(0, width_per_dev, body,
                                            (values, psd))
        values_out = values_in + jax.lax.psum(values_l - values_in, "data")
        psd_out = jax.lax.pmax(psd_l, "data")
        return values_out, psd_out

    smapped = jax.shard_map(
        device_run, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False)

    sds = jax.ShapeDtypeStruct
    args = (
        sds((n,), jnp.float32), sds((num_blocks,), jnp.float32),
        sds((width, e_cap), jnp.int32), sds((width, e_cap), jnp.int32),
        sds((width, e_cap), jnp.float32), sds((width, e_cap), jnp.bool_),
        sds((width,), jnp.int32), sds((width,), jnp.int32),
        sds((width,), jnp.bool_),
    )
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    shardings = (repl, repl, data, data, data, data, data, data, data)
    lowered = jax.jit(smapped, in_shardings=shardings,
                      out_shardings=(repl, repl)).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # jax 0.4.x: one dict per program
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    coll = parse_collectives(compiled.as_text())
    return {"status": "ok", "mesh": mesh_name, "devices": int(mesh.size),
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "collectives": coll,
            "collective_bytes": sum(v["bytes"] for v in coll.values()),
            "n_vertices": n, "num_blocks": num_blocks}


# Beyond-paper optimized preset (§Perf levers validated in the hillclimb).
# Ratio-preserving head pads only (padding must keep q:kv grouping exact);
# cast_weights_once pairs with save_dots (C3: cast alone regresses under
# full remat); embed_d_shard only for untied archs.
_COMMON = {"remat_policy": "save_dots", "cast_weights_once": True}
OPTIMIZED = {
    "deepseek_moe_16b": {**_COMMON, "embed_d_shard": True},
    "granite_moe_3b_a800m": {**_COMMON, "embed_d_shard": True,
                             "pad_experts_to": 48, "capacity_factor": 1.0},
    "qwen3_14b": {**_COMMON, "embed_d_shard": True,
                  "pad_q_heads_to": 48, "pad_kv_heads_to": 16},
    "yi_6b": {**_COMMON, "embed_d_shard": True},
    "llama3p2_1b": dict(_COMMON),          # tied embeddings: no d-shard
    "mistral_nemo_12b": {**_COMMON, "embed_d_shard": True},
    # phi3 (MHA kv=32): cast/dshard regressed collectives -> remat only
    "phi3_vision_4p2b": {"remat_policy": "save_dots"},
    "mamba2_2p7b": dict(_COMMON),          # tied
    "hymba_1p5b": dict(_COMMON),           # 25:5 heads: no exact pad
    "whisper_base": {**_COMMON, "embed_d_shard": True},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--graph", action="store_true",
                    help="also dry-run the graph engine sweep")
    ap.add_argument("--preset", default=None, choices=[None, "optimized"],
                    help="apply the §Perf optimized per-arch levers")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = configs.ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):  # --force reruns cells, never drops others
        with open(args.out) as f:
            results = json.load(f)

    def flush():
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)

    for multi in meshes:
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        mesh = make_production_mesh(multi_pod=multi)
        if args.graph:
            key = f"graph_pagerank/sweep/{mesh_name}"
            if key not in results or results[key].get("status") == "error":
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    results[key] = lower_graph_cell(mesh, mesh_name)
                except Exception as e:  # noqa: BLE001
                    results[key] = {"status": "error", "error": repr(e),
                                    "trace": traceback.format_exc()[-2000:]}
                flush()
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch}/{shape_name}/{mesh_name}"
                if key in results and results[key].get("status") != "error" \
                        and not args.force:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                t0 = time.perf_counter()
                override = (dict(OPTIMIZED.get(arch, {}))
                            if args.preset == "optimized" else None)
                if override and SHAPES[shape_name].kind == "decode":
                    # head pads double the KV cache: train/prefill only
                    override.pop("pad_q_heads_to", None)
                    override.pop("pad_kv_heads_to", None)
                try:
                    results[key] = lower_cell(arch, shape_name, mesh,
                                              mesh_name,
                                              cfg_override=override)
                except Exception as e:  # noqa: BLE001
                    results[key] = {"status": "error", "error": repr(e),
                                    "trace": traceback.format_exc()[-2000:]}
                print(f"[dryrun] {key}: {results[key]['status']} "
                      f"({time.perf_counter()-t0:.1f}s)", flush=True)
                flush()
    flush()
    bad = {k: v for k, v in results.items() if v.get("status") == "error"}
    print(f"[dryrun] done: {len(results)} cells, {len(bad)} errors")
    for k, v in bad.items():
        print(f"  ERROR {k}: {v['error']}")


if __name__ == "__main__":
    main()
