"""Training step + fault-tolerant loop.

The step is a single pjit'd program: microbatched grad accumulation
(lax.scan), optional remat (nothing_saveable over the layer scan), optional
error-feedback int8 gradient compression, global-norm clip, AdamW. Sharding
comes from the logical-axes rules (repro.distributed) — the same step runs
on 1 CPU device or a 512-chip multi-pod mesh unchanged.

Fault tolerance in the loop:
* checkpoint cadence (atomic; resume-latest on start),
* a step-time watchdog for straggler/step-time anomalies — at real scale a
  consistently slow step indicates a degraded host; the loop flags it and
  tightens checkpoint cadence (preemption-safe posture),
* elastic restart: checkpoints are mesh-agnostic (see repro.checkpoint).
"""
from __future__ import annotations

import dataclasses
import logging
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import restore_latest, save_checkpoint
from repro.configs.base import ArchConfig
from repro.distributed import sharding as shd
from repro.models import api
from repro.optim import compress as gcomp
from repro.optim.adamw import AdamWConfig, AdamWState, adamw_init, adamw_update

log = logging.getLogger("repro.train")


def resolve_attention_path(cfg: ArchConfig,
                           train_cfg: "TrainConfig") -> ArchConfig:
    """Apply the TrainConfig attention-kernel overrides to the arch config."""
    updates = {}
    if train_cfg.use_pallas is not None:
        updates["use_pallas"] = train_cfg.use_pallas
    if train_cfg.fuse_attention_features is not None:
        updates["fuse_attention_features"] = train_cfg.fuse_attention_features
    return dataclasses.replace(cfg, **updates) if updates else cfg


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1            # grad-accumulation steps
    remat: bool = True
    # Attention-kernel override for the training step. None = respect
    # cfg.use_pallas; True/False force the Pallas / jnp attention path.
    # The Pallas kernels carry custom VJPs (DESIGN.md §3), so use_pallas
    # training steps differentiate end to end — no inference-only fallback.
    use_pallas: bool | None = None
    fuse_attention_features: bool | None = None
    # "nothing" = nothing_saveable; "save_collectives" saves the named
    # post-all-reduce tensors (attn_out/mlp_out) so the backward recompute
    # skips re-running the forward TP collectives (§Perf).
    remat_policy: str = "nothing"
    compress_grads: bool = False     # error-feedback int8 (DP payload /4)
    ckpt_dir: str = os.path.join(tempfile.gettempdir(), "repro_ckpt")
    ckpt_every: int = 200
    watchdog_factor: float = 2.0     # step slower than factor x median -> flag
    keep_ckpts: int = 3


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig,
                    train_cfg: TrainConfig):
    """Returns train_step(params, opt_state, ef_state, batch) -> (...)"""

    cfg = resolve_attention_path(cfg, train_cfg)
    remat_arg = (train_cfg.remat_policy
                 if (train_cfg.remat and train_cfg.remat_policy != "nothing")
                 else train_cfg.remat)

    def compute_grads(params, batch):
        if train_cfg.microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                api.loss_fn, has_aux=True)(params, cfg, batch,
                                           remat=remat_arg)
            return loss, metrics, grads
        n = train_cfg.microbatches
        mb = jax.tree.map(
            lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch)

        def acc_step(carry, micro):
            loss_a, grads_a = carry
            (loss, _), grads = jax.value_and_grad(
                api.loss_fn, has_aux=True)(params, cfg, micro,
                                           remat=remat_arg)
            grads_a = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n, grads_a, grads)
            return (loss_a + loss / n, grads_a), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(acc_step, (jnp.zeros(()), zeros), mb)
        return loss, {"nll": loss, "moe_aux": jnp.zeros(())}, grads

    def train_step(params, opt_state: AdamWState, ef_state, batch):
        loss, metrics, grads = compute_grads(params, batch)
        if train_cfg.compress_grads:
            grads, ef_state = gcomp.compress_decompress(grads, ef_state)
        params, opt_state, opt_metrics = adamw_update(
            grads, opt_state, params, opt_cfg)
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return params, opt_state, ef_state, metrics

    return train_step


def jit_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig,
                   train_cfg: TrainConfig, mesh,
                   rules: shd.ShardingRules = shd.DEFAULT_RULES):
    """pjit the step with rule-derived in/out shardings + donation."""
    base_step = make_train_step(cfg, opt_cfg, train_cfg)

    def step(*args):
        # Install the activation-constraint context during tracing so
        # with_sharding_constraint picks up (mesh, rules).
        with shd.activation_sharding(mesh, rules):
            return base_step(*args)

    axes = api.param_axes(cfg)
    p_abs = api.abstract_params(cfg)
    p_sh = shd.logical_to_sharding(mesh, rules, p_abs, axes)
    opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), p_abs)
    m_sh = shd.logical_to_sharding(mesh, rules, opt_abs.m, axes)
    v_sh = shd.logical_to_sharding(mesh, rules, opt_abs.v, axes)
    from jax.sharding import NamedSharding, PartitionSpec
    scalar_sh = NamedSharding(mesh, PartitionSpec())
    opt_sh = AdamWState(scalar_sh, m_sh, v_sh)
    ef_sh = (shd.logical_to_sharding(mesh, rules, p_abs, axes)
             if train_cfg.compress_grads else scalar_sh)
    b_sh = shd.batch_sharding(mesh, rules)
    metric_sh = {k: scalar_sh for k in
                 ("nll", "moe_aux", "grad_norm", "lr", "loss")}
    return jax.jit(
        step,
        in_shardings=(p_sh, opt_sh, ef_sh, b_sh),
        out_shardings=(p_sh, opt_sh, ef_sh, metric_sh),
        donate_argnums=(0, 1, 2),
    )


class Trainer:
    """Fault-tolerant loop around the jit'd step."""

    def __init__(self, cfg: ArchConfig, opt_cfg: AdamWConfig,
                 train_cfg: TrainConfig, mesh,
                 rules: shd.ShardingRules = shd.DEFAULT_RULES, *, seed=0):
        self.cfg, self.opt_cfg, self.train_cfg = cfg, opt_cfg, train_cfg
        self.mesh, self.rules = mesh, rules
        self.step_fn = jit_train_step(cfg, opt_cfg, train_cfg, mesh, rules)
        key = jax.random.PRNGKey(seed)
        axes = api.param_axes(cfg)
        with mesh:
            self.params = shd.shard_params(
                mesh, rules, api.init_params(cfg, key), axes)
            self.opt_state = adamw_init(self.params, opt_cfg)
            self.ef_state = (gcomp.init(self.params)
                             if train_cfg.compress_grads
                             else jnp.zeros(()))
        self.step = 0
        self._times: list[float] = []
        self._resume()

    def _resume(self):
        state = {"params": self.params, "opt": self.opt_state}
        restored, step = restore_latest(self.train_cfg.ckpt_dir, state)
        if restored is not None:
            self.params = restored["params"]
            self.opt_state = restored["opt"]
            self.step = step
            log.info("resumed from step %d", step)

    def save(self):
        save_checkpoint(self.train_cfg.ckpt_dir, self.step,
                        {"params": self.params, "opt": self.opt_state},
                        keep=self.train_cfg.keep_ckpts)

    def run(self, batches, num_steps: int, *, log_every: int = 10):
        """batches: iterator of (step, batch). Returns metric history."""
        history = []
        ckpt_every = self.train_cfg.ckpt_every
        with self.mesh:
            for step, batch in batches:
                if step >= num_steps:
                    break
                t0 = time.monotonic()
                (self.params, self.opt_state, self.ef_state,
                 metrics) = self.step_fn(self.params, self.opt_state,
                                         self.ef_state, batch)
                metrics = jax.device_get(metrics)
                dt = time.monotonic() - t0
                self._times.append(dt)
                self.step = step + 1
                # Straggler / anomaly watchdog: tighten checkpoint cadence.
                med = sorted(self._times)[len(self._times) // 2]
                if (len(self._times) > 5
                        and dt > self.train_cfg.watchdog_factor * med):
                    log.warning("step %d took %.2fs (median %.2fs) — "
                                "tightening checkpoint cadence", step, dt, med)
                    ckpt_every = max(ckpt_every // 2, 10)
                if self.step % ckpt_every == 0:
                    self.save()
                history.append({"step": self.step, **metrics,
                                "step_time_s": dt})
                if step % log_every == 0:
                    log.info("step %d loss %.4f (%.2fs)", step,
                             float(metrics["loss"]), dt)
        self.save()
        return history
