"""End-to-end training driver: SLAYformer on synthetic LM data with the
full production substrate — sharded params, microbatching, remat,
checkpointing, resume, straggler watchdog.

CPU-reduced preset (default) trains a ~10M model for 200 steps in a few
minutes; the paper preset (--preset paper) is the 124M GPT-2-small-scale
SLAYformer from Table 5; --arch selects any of the 10 assigned
architectures (reduced smoke variant with --smoke).

    PYTHONPATH=src python examples/train_slayformer.py
    PYTHONPATH=src python examples/train_slayformer.py --steps 500 \
        --attn-kind softmax          # quadratic baseline, same budget
"""
import argparse
import dataclasses
import logging
import os
import tempfile

from repro import configs
from repro.data.pipeline import DataConfig, batch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainConfig, Trainer

logging.basicConfig(level=logging.INFO, format="%(message)s")


def build_config(args):
    if args.preset == "paper":
        cfg = configs.get_config("slayformer-124m")
    elif args.smoke or args.preset == "cpu":
        cfg = configs.get_smoke_config(args.arch)
        if args.preset == "cpu" and args.arch == "slayformer-124m":
            cfg = dataclasses.replace(cfg, num_layers=4, d_model=128,
                                      num_heads=4, num_kv_heads=4, d_ff=512,
                                      vocab_size=512, dtype="float32")
    else:
        cfg = configs.get_config(args.arch)
    if args.attn_kind:
        cfg = dataclasses.replace(cfg, attn_kind=args.attn_kind)
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="slayformer-124m",
                    choices=list(configs.ALL_ARCHS))
    ap.add_argument("--preset", default="cpu", choices=["cpu", "paper",
                                                        "full"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attn-kind", default=None)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "slayformer_ckpt"))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = build_config(args)
    print(f"arch={cfg.name} attn={cfg.attn_kind} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} params~{cfg.param_count_dense / 1e6:.1f}M")

    mesh = make_host_mesh()
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    train_cfg = TrainConfig(microbatches=args.microbatches, remat=False,
                            compress_grads=args.compress_grads,
                            ckpt_dir=args.ckpt_dir, ckpt_every=50)
    trainer = Trainer(cfg, opt_cfg, train_cfg, mesh)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    history = trainer.run(batch_iterator(dcfg, start_step=trainer.step),
                          num_steps=args.steps, log_every=10)
    if history:
        first, last = history[0]["loss"], history[-1]["loss"]
        print(f"\nloss {first:.4f} -> {last:.4f} over {len(history)} steps "
              f"(resume from step {trainer.step} by re-running)")


if __name__ == "__main__":
    main()
