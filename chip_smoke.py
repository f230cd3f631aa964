"""Chip smoke test: slayformer-124m served and trained on a TPU, at its
published widths, through the normal entry points, with the SLAY layers on
compiled Pallas kernels.

    python chip_smoke.py                # one chip: phases a-e
    python chip_smoke.py --four-chips   # four chips: phase f only

Phases run in this one process (a chip belongs to one process):

  a. device check — anything but a TPU is refused (non-zero exit);
  b. ContinuousServingEngine, SLAY constant-state regime, random bf16
     weights from ``--seed``: 16 requests, 128-1024 prompt tokens, 64 new
     tokens each; the decode macro-step must contain the Pallas kernel;
  c. the same trace on the softmax KV-ring regime;
  d. kernels against their jnp references at full width, and whole-model
     prefill + 8 decode logits with the kernels on and off;
  e. three Trainer steps through the fused kernel's custom VJP;
  f. (``--four-chips`` only) phase b's trace on a 4-way slot-sharded pool
     against a one-device pool in the same process: equal stream digests,
     no collectives in the sharded decode step.

Each phase prints one line: what it did, its compile seconds (JAX's own
trace + lower + backend-compile events, cache reads included) and its run
seconds (phase wall time less compile). No phase is wrapped in a handler:
a failure raises, the exit code is non-zero, and the result line is not
printed. The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.analysis import hlo as hlo_lib  # noqa: E402
from repro.configs.base import ServingConfig  # noqa: E402
from repro.core.features import slay_features  # noqa: E402
from repro.data.pipeline import (DataConfig, batch_iterator,  # noqa: E402
                                 make_batch)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh, make_serving_mesh  # noqa: E402
from repro.models import api  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.serving.engine import ContinuousServingEngine, Request  # noqa: E402
from repro.train.loop import TrainConfig, Trainer  # noqa: E402

ARCH = "slayformer-124m"
KERNEL = "tpu_custom_call"       # how a compiled Pallas kernel shows in HLO
# Serving geometry (phases b, c, f).
SLOTS, MAX_LEN, PREFILL_CHUNK, MACRO_TICKS = 32, 2048, 256, 8
REQUESTS, NEW_TOKENS = 16, 64
# Prompt lengths are multiples of 128 in [128, 1024]: every prefill chunk is
# then 128 or 256 tokens long, so the chunk program compiles twice, not once
# per distinct ragged tail.
PROMPT_LENGTHS = tuple(range(128, 1025, 128))
# Training (phase e).
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 3

_compile_s = [0.0]


def _on_event(event: str, duration: float, **_):
    if event.startswith("/jax/core/compile/"):
        _compile_s[0] += duration


class Phase:
    """Times one phase; prints its line on a clean exit only."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def done(self, what: str):
        self.what = what

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = _compile_s[0] - self.c0
            print(f"[{self.name}] {self.what} | compile {comp:.2f} s | "
                  f"run {wall - comp:.2f} s", flush=True)
        return False


def _rel_err(got, want) -> float:
    """max |got - want| over max |want|: error on the output's own scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _check(name: str, err: float, tol: float, why: str) -> str:
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3e} > tolerance {tol:.0e}"
                             f" ({why})")
    return f"{name} {err:.2e} <= {tol:.0e}"


def _highest():
    return jax.default_matmul_precision("highest")


def _trace(cfg, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(3, cfg.vocab_size,
                                 size=int(rng.choice(PROMPT_LENGTHS))
                                 ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS, arrival_time=float(2 * i))
            for i in range(REQUESTS)]


def _digest(outs: dict) -> str:
    h = hashlib.sha256()
    for rid in sorted(outs):
        h.update(np.int64(rid).tobytes())
        h.update(np.asarray(outs[rid], np.int32).tobytes())
    return h.hexdigest()


def _serve(cfg, params, mesh, seed: int, slot_shards: int = 0):
    """Run the trace on a fresh engine; check completion, vocab range and
    leaks. Returns (engine, outputs)."""
    eng = ContinuousServingEngine(
        cfg, params, mesh,
        serving=ServingConfig(num_slots=SLOTS, max_len=MAX_LEN,
                              prefill_chunk=PREFILL_CHUNK,
                              macro_ticks=MACRO_TICKS,
                              slot_shards=slot_shards))
    outs, summary = eng.run(_trace(cfg, seed))
    assert summary["requests_completed"] == REQUESTS, summary
    assert len(outs) == REQUESTS, sorted(outs)
    for rid, toks in outs.items():
        toks = np.asarray(toks)
        assert len(toks) == NEW_TOKENS, (rid, len(toks))
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all(), (rid, toks)
    assert summary["final_occupancy"] == 0, summary["final_occupancy"]
    assert summary["final_queue_depth"] == 0, summary["final_queue_depth"]
    assert summary["final_pages_in_use"] == 0, summary["final_pages_in_use"]
    return eng, outs


def phase_serving(cfg, params, seed: int, label: str, *, kernel: bool):
    with Phase(label) as ph:
        eng, outs = _serve(cfg, params, make_host_mesh(), seed)
        has_kernel = KERNEL in eng.decode_hlo()
        assert has_kernel == kernel, (
            f"decode step {'lacks' if kernel else 'has'} the Pallas kernel")
        ph.done(f"{cfg.attn_kind}: {REQUESTS}/{REQUESTS} requests x "
                f"{NEW_TOKENS} tokens on {SLOTS} slots, max_len {MAX_LEN}, "
                f"no leaks, decode kernel={has_kernel}, digest "
                f"{_digest(outs)[:16]}")


def phase_kernels(cfg, params, seed: int):
    """Compiled kernels against their jnp references at full width. The
    references run at fp32 matmul precision ("highest"), so each error
    below is the kernel's own."""
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, ks, kt = jax.random.split(key, 5)
    scfg = cfg.slay_config()
    m, d, h = scfg.feature_dim, cfg.resolved_head_dim, cfg.num_heads
    lines = []
    with Phase("d/kernels") as ph:
        # 1. Masked decode step on a full serving pool: features as the
        #    model makes them (bf16), a state summed over 512 past tokens.
        feats = jax.jit(lambda u: slay_features(u, params["slay"], scfg))
        qf = feats(jax.random.normal(kq, (SLOTS, h, d), jnp.bfloat16))
        kf = feats(jax.random.normal(kk, (SLOTS, h, d), jnp.bfloat16))
        v = jax.random.normal(kv, (SLOTS, h, d), jnp.bfloat16)
        kpast = feats(jax.random.normal(ks, (SLOTS, 512, h, d), jnp.bfloat16))
        vpast = jax.random.normal(kt, (SLOTS, 512, h, d), jnp.bfloat16)
        with _highest():
            s = jnp.einsum("blhm,blhd->bhmd", kpast.astype(jnp.float32),
                           vpast.astype(jnp.float32))
            z = jnp.sum(kpast.astype(jnp.float32), axis=1)
        active = jnp.arange(SLOTS) % 3 != 0
        y_k, s_k, z_k = jax.jit(ops.decode_linear_step)(qf, kf, v, s, z,
                                                        active)
        with _highest():
            y_r, s_r, z_r = jax.jit(ref.decode_linear_attention_ref)(
                qf.reshape(-1, m), kf.reshape(-1, m), v.reshape(-1, d),
                s.reshape(-1, m, d), z.reshape(-1, m),
                jnp.repeat(active, h))
        # State update is elementwise fp32 adds in both: rounding only.
        lines.append(_check("decode state", max(
            _rel_err(s_k.reshape(s_r.shape), s_r),
            _rel_err(z_k.reshape(z_r.shape), z_r)), 1e-6,
            "elementwise fp32 adds, identical inputs"))
        # Readout: (G, m) @ (m, dv) fp32 on the MXU; its passes may round
        # operands to bf16 (2^-9 relative each), output stored as bf16.
        lines.append(_check("decode y", _rel_err(y_k.reshape(y_r.shape),
                                                 y_r), 1e-2,
                            "bf16 output, MXU fp32 passes"))

        # 2. Fused forward at L = 2048 against features + chunked scan.
        L = 2048
        q = jax.random.normal(kq, (2, L, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (2, L, h, d), jnp.bfloat16)
        vv = jax.random.normal(kv, (2, L, h, d), jnp.bfloat16)

        def fused(q, k, v, use):
            if use:
                return ops.slay_fused_attention(q, k, v, params["slay"], scfg)
            from repro.core import linear_attention as la
            return la.causal_chunked(slay_features(q, params["slay"], scfg),
                                     slay_features(k, params["slay"], scfg),
                                     v, chunk_size=cfg.chunk_size)
        y_f = jax.jit(fused, static_argnums=3)(q, k, vv, True)
        with _highest():
            y_j = jax.jit(fused, static_argnums=3)(
                q.astype(jnp.float32), k.astype(jnp.float32),
                vv.astype(jnp.float32), False)
        # The kernel keeps Ψ and the chunk scores in fp32 VMEM; the output
        # is bf16 (2^-9) and its matmuls may take bf16 MXU passes.
        lines.append(_check("fused fwd L=2048", _rel_err(y_f, y_j), 2e-2,
                            "bf16 output, MXU fp32 passes over 2048 tokens"))

        # 3. Whole model, prefill (fused kernel) + 8 decode steps (decode
        #    kernel), kernels on against kernels off (the jnp path).
        tokens = jax.random.randint(ks, (2, 512), 3, cfg.vocab_size)

        def run_model(use_pallas: bool):
            c = dataclasses.replace(cfg, use_pallas=use_pallas)
            pre = jax.jit(lambda p, t: api.prefill(p, c, {"tokens": t},
                                                   max_len=1024))
            dec = jax.jit(lambda p, cache, t: api.decode_step(p, c, cache, t))
            logits, cache = pre(params, tokens)
            out = [logits[:, -1]]
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            for _ in range(8):
                lg, cache = dec(params, cache, tok)
                out.append(lg[:, -1])
                tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
            return jnp.stack(out).astype(jnp.float32), tok
        on, _ = run_model(True)
        off, _ = run_model(False)
        assert bool(jnp.all(jnp.isfinite(on))), "non-finite kernel logits"
        # Same bf16 weights and activations; the paths round at different
        # points (fp32 VMEM features vs bf16 features in HBM) and that
        # compounds over 12 layers: a few bf16 ulps of the logit scale.
        lines.append(_check("model logits prefill+8 decode",
                            _rel_err(on, off), 5e-2,
                            "bf16 activations rounded at different points"))
        ph.done("; ".join(lines))


def phase_training(cfg, seed: int):
    with tempfile.TemporaryDirectory() as ckpt, Phase("e/training") as ph:
        trainer = Trainer(cfg, AdamWConfig(lr=3e-4, warmup_steps=1,
                                           total_steps=TRAIN_STEPS),
                          TrainConfig(ckpt_dir=ckpt, ckpt_every=10_000),
                          make_host_mesh(), seed=seed)
        assert trainer.step == 0, "fresh checkpoint dir resumed a run"
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                          global_batch=TRAIN_BATCH, seed=seed)
        batch = make_batch(dcfg, 0)
        with trainer.mesh:
            text = trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                         trainer.ef_state, batch
                                         ).compile().as_text()
        assert KERNEL in text, "train step lacks the Pallas kernel"
        hist = trainer.run(batch_iterator(dcfg), num_steps=TRAIN_STEPS,
                           log_every=10_000)
        losses = [float(r["loss"]) for r in hist]
        assert len(losses) == TRAIN_STEPS, losses
        assert all(np.isfinite(losses)), losses
        ph.done(f"{TRAIN_STEPS} steps, batch {TRAIN_BATCH} x seq "
                f"{TRAIN_SEQ}, loss {' -> '.join(f'{x:.4f}' for x in losses)}"
                f", train step kernel=True")


def phase_four_chips(cfg, params, seed: int):
    with Phase("f/four-chips") as ph:
        one, outs1 = _serve(cfg, params, make_serving_mesh(1), seed)
        four, outs4 = _serve(cfg, params, make_serving_mesh(4), seed,
                             slot_shards=4)
        assert four.slot_shards == 4, four.slot_shards
        d1, d4 = _digest(outs1), _digest(outs4)
        assert d1 == d4, (d1, d4)
        # Placement: every pool leaf spans the four devices, one slot block
        # of SLOTS / 4 each.
        for leaf in jax.tree.leaves(four.pool):
            if leaf.ndim < 2 or leaf.shape[1] != SLOTS:
                continue
            devs = {sh.device for sh in leaf.addressable_shards}
            assert len(devs) == 4, (leaf.shape, devs)
            for sh in leaf.addressable_shards:
                assert sh.data.shape[1] == SLOTS // 4, sh.data.shape
        text = four.decode_hlo()
        assert KERNEL in text, "sharded decode step lacks the Pallas kernel"
        found = hlo_lib.check_no_collectives(hlo_lib.parse_hlo(text),
                                             "sharded decode")
        assert not found, [f.render() for f in found]
        ph.done(f"{REQUESTS} requests on 4 slot shards vs 1 device: digests "
                f"equal ({d4[:16]}), 0 collectives in the sharded decode "
                f"step, pool placed {SLOTS // 4} slots per device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way slot-sharded serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
              f"refusing to run elsewhere", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(jax.devices()) < need:
        print(f"chip_smoke: needs {need} TPU devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    with Phase("a/device") as ph:
        import jaxlib
        import libtpu
        ph.done(f"{dev.device_kind} x {len(jax.devices())}, jax "
                f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
                f"{getattr(libtpu, '__version__', 'unknown')}, compile cache "
                f"{cache_dir}")
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    cfg = configs.get_config(ARCH)
    assert cfg.use_pallas, "the chip path must dispatch the Pallas kernels"
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.four_chips:
        phase_four_chips(cfg, params, args.seed)
    else:
        phase_serving(cfg, params, args.seed, "b/serve-slay", kernel=True)
        soft = dataclasses.replace(cfg, attn_kind="softmax")
        phase_serving(soft, api.init_params(soft, jax.random.PRNGKey(
            args.seed)), args.seed, "c/serve-softmax", kernel=False)
        phase_kernels(cfg, params, args.seed)
        phase_training(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
