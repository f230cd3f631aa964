import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, and fits — and extract the roofline terms from the compiled
artifact. No arrays are ever allocated: parameters, optimizer state, decode
caches and batches are all ShapeDtypeStruct stand-ins.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape train_4k [--multi-pod] [--attn-kind softmax] \
        [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out grid.json

Exit code != 0 on any failed cell (sharding mismatch, OOM at compile,
unsupported collective) — those are bugs in the system, per the assignment.
"""  # noqa: E402
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ArchConfig, ShapeCell
from repro.distributed import sharding as shd
from repro.launch import roofline as rl
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import api
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.loop import TrainConfig, make_train_step


def _struct_batch(cfg: ArchConfig, cell: ShapeCell) -> dict:
    return configs.input_specs(cfg, cell)


def _cell_is_skipped(cfg: ArchConfig, cell: ShapeCell) -> str | None:
    """Assignment skip rules. With SLAY as the default backend no cell is
    skipped (long_500k is exactly what SLAY enables); pure full-attention
    variants skip long_500k."""
    if cell.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        spec_linear = cfg.attn_kind in ("slay", "favor", "cosformer", "elu1")
        if not spec_linear:
            return ("long_500k needs sub-quadratic attention; "
                    f"attn_kind={cfg.attn_kind} is full-attention "
                    "(run with the SLAY backend instead)")
    return None


def default_microbatches(cfg: ArchConfig, cell: ShapeCell, mesh) -> int:
    """Grad-accumulation factor so one microbatch is ~4k tokens per
    data-parallel shard — keeps activation residency << HBM without
    starving the MXU. Must divide the per-shard batch."""
    data_par = 1
    for ax in ("pod", "data"):
        data_par *= mesh.shape.get(ax, 1)
    per_shard_seqs = max(cell.global_batch // data_par, 1)
    tokens_per_shard = per_shard_seqs * cell.seq_len
    want = max(1, tokens_per_shard // 4096)
    mb = min(want, per_shard_seqs)
    while per_shard_seqs % mb:
        mb -= 1
    return max(mb, 1)


def lower_cell(cfg: ArchConfig, cell: ShapeCell, mesh,
               rules: shd.ShardingRules = shd.DEFAULT_RULES, *,
               train_cfg: TrainConfig | None = None,
               opt_cfg: AdamWConfig | None = None):
    """Build + lower the cell's step function. Returns `lowered`."""
    axes = api.param_axes(cfg)
    p_abs = api.abstract_params(cfg)
    fallback_log: list = []
    p_sh = shd.logical_to_sharding(mesh, rules, p_abs, axes, fallback_log)
    b_specs = _struct_batch(cfg, cell)
    b_sh = shd.batch_sharding(mesh, rules, batch_size=cell.global_batch)
    b_shard = {k: b_sh for k in b_specs}

    if cell.mode == "train":
        train_cfg = train_cfg or TrainConfig(
            microbatches=default_microbatches(cfg, cell, mesh), remat=True,
            compress_grads=False)
        opt_cfg = opt_cfg or AdamWConfig(
            moment_dtype="bfloat16"
            if cfg.param_count_dense > 1e11 else "float32")
        step = make_train_step(cfg, opt_cfg, train_cfg)
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), p_abs)
        m_sh = shd.logical_to_sharding(mesh, rules, opt_abs.m, axes)
        v_sh = shd.logical_to_sharding(mesh, rules, opt_abs.v, axes)
        from jax.sharding import NamedSharding, PartitionSpec
        sc = NamedSharding(mesh, PartitionSpec())
        opt_sh = type(opt_abs)(sc, m_sh, v_sh)
        if train_cfg.compress_grads:
            from repro.optim import compress as gcomp
            ef_abs = jax.eval_shape(gcomp.init, p_abs)
            ef_sh = shd.logical_to_sharding(mesh, rules, ef_abs, axes)
        else:
            ef_abs = jax.ShapeDtypeStruct((), jnp.float32)
            ef_sh = sc
        fn = jax.jit(step, in_shardings=(p_sh, opt_sh, ef_sh, b_shard),
                     out_shardings=(p_sh, opt_sh, ef_sh, None),
                     donate_argnums=(0, 1))
        with mesh, shd.activation_sharding(mesh, rules):
            lowered = fn.lower(p_abs, opt_abs, ef_abs, b_specs)
    elif cell.mode == "prefill":
        fn = jax.jit(lambda p, b: api.prefill(p, cfg, b),
                     in_shardings=(p_sh, b_shard))
        with mesh, shd.activation_sharding(mesh, rules):
            lowered = fn.lower(p_abs, b_specs)
    else:  # decode
        c_abs = api.abstract_cache(cfg, cell.global_batch, cell.seq_len)
        c_sh = shd.cache_sharding(mesh, rules, c_abs)
        fn = jax.jit(lambda p, c, t: api.decode_step(p, cfg, c, t),
                     in_shardings=(p_sh, c_sh, b_shard["tokens"]),
                     out_shardings=(b_shard["tokens"], c_sh),
                     donate_argnums=(1,))
        with mesh, shd.activation_sharding(mesh, rules):
            lowered = fn.lower(p_abs, c_abs, b_specs["tokens"])
    return lowered, fallback_log


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             attn_kind: str | None = None,
             rules: shd.ShardingRules = shd.DEFAULT_RULES,
             train_cfg: TrainConfig | None = None,
             opt_cfg: AdamWConfig | None = None,
             mesh_shape: tuple[int, ...] | None = None,
             verbose: bool = True, **cfg_overrides) -> dict:
    cell = configs.get_cell(shape)
    overrides = dict(cfg_overrides)
    if attn_kind:
        overrides["attn_kind"] = attn_kind
    cfg = configs.get_config(arch, **overrides) if overrides \
        else configs.get_config(arch)
    record: dict = {"arch": arch, "shape": shape,
                    "mesh": ("x".join(map(str, mesh_shape)) if mesh_shape
                             else ("2x16x16" if multi_pod else "16x16")),
                    "attn_kind": cfg.attn_kind, "mode": cell.mode}
    skip = _cell_is_skipped(cfg, cell)
    if skip:
        record["status"] = "skipped"
        record["reason"] = skip
        return record
    if mesh_shape is not None:
        # Same 256-chip pod (or 512-chip 2-pod), different logical split —
        # e.g. (32, 8) so a 24-head/8-kv arch shards instead of replicating.
        axes = ("pod", "data", "model")[-len(mesh_shape):]
        mesh = make_mesh(mesh_shape, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.monotonic()
    try:
        lowered, fallbacks = lower_cell(cfg, cell, mesh, rules,
                                        train_cfg=train_cfg, opt_cfg=opt_cfg)
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — report per-cell failures
        record["status"] = "FAILED"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc(limit=8)
        return record
    t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    totals = rl.hlo_cost.analyze(compiled.as_text())
    roof = rl.Roofline(
        flops=totals.flops, hbm_bytes=totals.hbm_bytes,
        coll_bytes=totals.coll_wire_bytes, chips=chips,
        model_flops=rl.model_flops_for(cfg, cell),
        coll_by_kind=totals.coll_by_kind)
    top_dots = sorted(totals.dot_flops_by_meta.items(),
                      key=lambda kv: -kv[1])[:10]
    record.update({
        "status": "ok",
        "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "argument": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak": int(getattr(mem, "temp_size_in_bytes", 0))
            + int(getattr(mem, "argument_size_in_bytes", 0)),
        },
        "collectives": roof.coll_by_kind,
        "sharding_fallbacks": [f"{log}:{dim}!%{ax}" for log, dim, ax
                               in (fallbacks or [])][:20],
        "roofline": roof.report(),
        "top_dot_flops": [{"op": k, "flops": v} for k, v in top_dots],
    })
    if verbose:
        bpd = record["bytes_per_device"]
        print(f"[{record['mesh']}] {arch} x {shape}: OK "
              f"compile={t_compile:.0f}s "
              f"args={bpd['argument'] / 2**30:.2f}GiB "
              f"temp={bpd['temp'] / 2**30:.2f}GiB "
              f"dom={roof.dominant} "
              f"t=({roof.t_compute:.2e},{roof.t_memory:.2e},"
              f"{roof.t_collective:.2e})s "
              f"roofline_frac={roof.roofline_fraction:.2f}")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    choices=list(configs.ALL_ARCHS) + [None])
    ap.add_argument("--shape", default=None,
                    choices=[c.name for c in configs.SHAPE_CELLS] + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="full assigned grid (10 archs x 4 shapes)")
    ap.add_argument("--attn-kind", default=None)
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args(argv)

    cells: list[tuple[str, str, bool]] = []
    archs = [args.arch] if args.arch else list(configs.ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else \
        [c.name for c in configs.SHAPE_CELLS]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    records = []
    failed = 0
    for a, s, mp in cells:
        rec = run_cell(a, s, multi_pod=mp, attn_kind=args.attn_kind)
        records.append(rec)
        if rec["status"] == "FAILED":
            failed += 1
            print(f"[{'2x16x16' if mp else '16x16'}] {a} x {s}: FAILED — "
                  f"{rec['error']}", file=sys.stderr)
        elif rec["status"] == "skipped":
            print(f"[{'2x16x16' if mp else '16x16'}] {a} x {s}: skipped — "
                  f"{rec['reason']}")
        if args.out:
            with open(args.out, "w") as f:
                json.dump(records, f, indent=1)
    print(f"\n{len(records) - failed}/{len(records)} cells passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
