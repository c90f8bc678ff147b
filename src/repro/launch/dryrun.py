import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: prove the distribution config is coherent by
``.lower().compile()``-ing every (architecture x input-shape x mesh)
combination on 512 placeholder host devices.

Per combination this produces:
  * the compiled SPMD program (compile success == sharding coherence),
  * ``compiled.memory_analysis()``  -> per-device bytes (proves it fits),
  * ``compiled.cost_analysis()``    -> HLO FLOPs / bytes (roofline input),
  * collective statistics parsed from the optimized HLO text,
  * optional "probe" lowerings with 1 and 2 UNROLLED pattern periods —
    XLA's cost analysis counts while-loop bodies ONCE, so the scanned
    lowering undercounts depth; probes give exact per-period HLO numbers,
    from which a full depth extrapolates as
        total ~= probe1 + (P - 1) * (probe2 - probe1).

Usage:
  python -m repro.launch.dryrun --arch qwen2_5_14b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--probes]
Results accumulate into reports/dryrun/<mesh>/<arch>__<shape>.json.
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get, list_archs
from repro.configs.base import ArchConfig
from repro.core import GraphMultiTask, band_graph
from repro.launch.mesh import make_production_mesh, mesh_axes
from repro.launch.specs import INPUT_SHAPES, InputShape, input_specs
from repro.models import TransformerLM
from repro.optim import adamw
from repro.sharding.rules import (
    MeshAxes,
    batch_specs,
    cache_specs,
    param_specs,
    train_state_specs,
)
from repro.train.trainer import TrainState, init_state, make_train_step

ARCHS = [a for a in list_archs() if a != "multitask_linreg"]

# long_500k runs only for sub-quadratic archs (DESIGN.md §5 policy)
def applicable_shapes(cfg: ArchConfig) -> list[str]:
    shapes = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.long_context_ok:
        shapes.append("long_500k")
    return shapes


# ------------------------------------------------------------ HLO parsing
_COLL_RE = re.compile(
    r"(\w+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-op-kind totals of result sizes + estimated per-device wire bytes
    (ring algorithms). Loop bodies are counted once — see module docstring."""
    stats: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        size = _shape_bytes(shape_str)
        gm = _GROUPS_RE.search(line)
        g = len(gm.group(1).split(",")) if gm else 1
        if g <= 1:
            g = 2  # conservative
        if kind == "all-gather":
            wire = size * (g - 1) // g
        elif kind == "all-reduce":
            wire = 2 * size * (g - 1) // g
        elif kind == "reduce-scatter":
            wire = size * (g - 1)  # result is the scattered shard
        elif kind == "all-to-all":
            wire = size * (g - 1) // g
        else:  # collective-permute
            wire = size
        s = stats.setdefault(kind, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        s["count"] += 1
        s["result_bytes"] += size
        s["wire_bytes"] += wire
    stats["total_wire_bytes"] = sum(
        v["wire_bytes"] for k, v in stats.items() if isinstance(v, dict)
    )
    return stats


# ------------------------------------------------------------- lowering
def prepare(cfg: ArchConfig, shape: InputShape, ax: MeshAxes, mesh,
            microbatches: int = 1):
    """Build (fn, arg_sds, in_shardings, donate) for this (arch, shape)."""
    model = TransformerLM(cfg, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    batch_sds = input_specs(cfg, shape)
    bspecs = batch_specs(cfg, batch_sds, ax)

    def shardings(tree, specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    if shape.kind == "train":
        optimizer = adamw(3e-4)
        gmt = GraphMultiTask(
            band_graph(cfg.num_tasks, 1), eta=0.1, tau=1.0
        )
        step_fn = make_train_step(
            model, optimizer, multitask=gmt, microbatches=microbatches
        )
        state_sds = jax.eval_shape(lambda k: init_state(model, optimizer, k), key)
        sspecs = train_state_specs(cfg, state_sds, ax)
        fn = step_fn
        args = (state_sds, batch_sds)
        in_sh = (shardings(state_sds, sspecs), shardings(batch_sds, bspecs))
        return fn, args, in_sh, (0,)  # donate the TrainState

    params_sds = jax.eval_shape(model.init, key)
    pspecs = param_specs(cfg, params_sds, ax)
    if shape.kind == "prefill":
        fn = lambda p, b: model.prefill(p, b, shape.seq_len)
        args = (params_sds, batch_sds)
        in_sh = (shardings(params_sds, pspecs), shardings(batch_sds, bspecs))
        return fn, args, in_sh, ()

    # decode: one token against a cache of seq_len
    caches_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len)
    )
    cspecs = cache_specs(cfg, caches_sds, ax)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    fn = model.decode_step
    args = (params_sds, batch_sds, caches_sds, pos_sds)
    in_sh = (
        shardings(params_sds, pspecs),
        shardings(batch_sds, bspecs),
        shardings(caches_sds, cspecs),
        NamedSharding(mesh, P()),
    )
    return fn, args, in_sh, (2,)  # donate the caches


def lower_and_compile(cfg, shape, ax, mesh, save_hlo_to=None, microbatches=1):
    fn, args, in_sh, donate = prepare(cfg, shape, ax, mesh,
                                      microbatches=microbatches)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            fn, in_shardings=in_sh, donate_argnums=donate
        ).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # list[dict] on current JAX
        cost = cost[0] if cost else {}
    cost = cost or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    if save_hlo_to:
        with open(save_hlo_to, "w") as f:
            f.write(hlo)
    return {
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        },
        "collectives": coll,
    }


def probe_cfg(cfg: ArchConfig, shape: InputShape, periods: int) -> ArchConfig:
    """Unrolled small-depth variant for exact HLO cost probes."""
    return dataclasses.replace(
        cfg,
        num_layers=cfg.period * periods,
        unroll=True,
        remat=False,
        q_chunk=shape.seq_len,  # single q-chunk -> no undercounted scan
    )


def run_one(arch: str, shape_name: str, multi_pod: bool, probes: bool,
            out_dir: str, activation_sharding=None) -> dict:
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ax = mesh_axes(multi_pod=multi_pod)
    fsdp = tuple(ax.fsdp) if len(ax.fsdp) > 1 else ax.fsdp[0]
    batch_ax = fsdp if shape.global_batch % ax.fsdp_size == 0 else None
    if activation_sharding is None:
        # baseline: batch on fsdp, d_model on model — the residual stream is
        # fully 2-D sharded so per-layer saves stay O(B S d / chips)
        activation_sharding = (batch_ax, None, ax.model)
    cfg = dataclasses.replace(
        get(arch),
        num_tasks=ax.fsdp_size,
        moe_groups=ax.fsdp_size,  # shard-local MoE dispatch per data shard
        activation_sharding=activation_sharding,
        logits_sharding=(batch_ax, None, ax.model),
    )
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_layers": cfg.num_layers, "period": cfg.period,
        "num_periods": cfg.num_periods, "remainder": len(cfg.remainder),
    }
    result["scanned"] = lower_and_compile(cfg, shape, ax, mesh)
    if probes:
        for n in (1, 2):
            result[f"probe{n}"] = lower_and_compile(
                probe_cfg(cfg, shape, n), shape, ax, mesh
            )
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--probes", action="store_true")
    ap.add_argument("--act-shard", action="store_true",
                    help="constrain the residual stream to (data, None, model)")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    mesh_name = "multipod" if args.multi_pod else "singlepod"
    out_dir = os.path.join(args.out, mesh_name)
    act = ("data", None, "model") if args.act_shard else None

    combos = []
    if args.all:
        for a in ARCHS:
            for s in applicable_shapes(get(a)):
                combos.append((a, s))
    else:
        assert args.arch and args.shape
        combos = [(args.arch, args.shape)]

    ok, failed = 0, []
    for a, s in combos:
        t0 = time.time()
        try:
            r = run_one(a, s, args.multi_pod, args.probes, out_dir,
                        activation_sharding=act)
            mem = r["scanned"]["memory"]
            tot = sum(v or 0 for k, v in mem.items() if k != "code_bytes")
            print(
                f"OK   {a:22s} {s:12s} mesh={r['mesh']:8s} "
                f"compile={r['scanned']['compile_s']:7.1f}s "
                f"mem/device={tot/2**30:7.2f} GiB "
                f"flops={r['scanned']['cost']['flops'] or 0:.3e} "
                f"coll={r['scanned']['collectives']['total_wire_bytes']/2**20:9.1f} MiB",
                flush=True,
            )
            ok += 1
        except Exception as e:
            print(f"FAIL {a:22s} {s:12s}: {e}", flush=True)
            traceback.print_exc()
            failed.append((a, s, str(e)))
    print(f"\n{ok}/{len(combos)} combinations compiled on mesh {mesh_name}")
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
