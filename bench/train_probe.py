"""Does the program's training step move the parameters? A reading for
the training cell the benchmark does not hold yet.

The program's ``Trainer`` step (bf16 parameters, AdamW at its defaults,
remat on, synthetic Zipf batches) runs a few steps from the seed; beside
it runs the witness: the same loss, gradients and AdamW update from the
same bf16 weights, applied to a float32 master copy of the parameters
(the program's model takes bf16 weights only), so the update is kept at
a precision that can hold it. Per step it prints, for each leaf, the
share of its elements that differ from the start, the loss, the step
time and the compile accounting (so a second process shows whether the
step was found in the persistent cache). The weights are the bf16 leaves
that do not start at zero; the norm scales start at zero, and the SLAY
feature bank (anchors, omegas) is float32 and gets no update.

    python3 bench/train_probe.py --seed N [--batch 2 --seq 4096 --steps 3]
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def moved_shares(arch: dict, seed: int, batch: int, seq: int, steps: int,
                 dtype: str, monitor=None):
    """One line per step, with parameters kept in ``dtype``: per leaf, the
    share of its elements that differ from the start."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import serving
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.distributed import sharding as shd
    from repro.models import api
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
    from repro.train.loop import TrainConfig, Trainer

    cfg = serving.arch_config({"name": "probe", "arch": arch})
    opt = AdamWConfig()
    # Never written: the probe calls the step, not the loop that saves.
    ckpt = os.path.join(ROOT, ".train_probe_ckpt")
    tr = Trainer(cfg, opt, TrainConfig(ckpt_dir=ckpt, ckpt_every=10**9),
                 make_host_mesh(), seed=seed)
    params, opt_state = tr.params, tr.opt_state
    step_fn = tr.step_fn
    if dtype != "bfloat16":
        kinds = jax.tree.map(lambda p: p.dtype, params)
        params = jax.tree.map(lambda p: p.astype(dtype), params)
        opt_state = adamw_init(params, opt)

        @jax.jit
        def step_fn(p, st, ef, batch):
            with shd.activation_sharding(tr.mesh, tr.rules):
                run_p = jax.tree.map(lambda x, k: x.astype(k), p, kinds)
                (loss, _), g = jax.value_and_grad(api.loss_fn, has_aux=True)(
                    run_p, cfg, batch, remat=True)
                p, st, m = adamw_update(g, st, p, opt)
            return p, st, ef, {**m, "loss": loss}
    dcfg = DataConfig(vocab_size=arch["vocab_size"], seq_len=seq,
                      global_batch=batch, seed=seed)
    share = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.mean((x != y).astype(jnp.float32)), a, b))
    start = jax.tree.map(jnp.copy, params)
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    out = []
    with tr.mesh:
        for step in range(steps):
            b = make_batch(dcfg, step)
            t0 = time.perf_counter()
            params, opt_state, tr.ef_state, m = step_fn(
                params, opt_state, tr.ef_state, b)
            jax.block_until_ready(params)
            dt = time.perf_counter() - t0
            moved = [float(x) for x in jax.tree.leaves(share(params, start))]
            line = {"dtype": dtype, "seed": seed, "step": step + 1,
                    "loss": float(m["loss"]), "lr": float(m["lr"]),
                    "step_s": round(dt, 4),
                    "changed_share_since_start": dict(zip(names, moved))}
            if monitor is not None:
                line["compile"] = monitor.snapshot()
            out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import compiles, run, spec
    run.setup_jax(1)
    monitor = compiles.Monitor()
    cfg = json.load(open(spec.config_path("slayformer-124m",
                                          spec.load_benchmark())))
    for dtype in ("bfloat16", "float32"):
        for line in moved_shares(cfg["arch"], args.seed, args.batch,
                                 args.seq, args.steps, dtype, monitor):
            line["since_process_start_s"] = round(
                time.perf_counter() - T_START, 2)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
