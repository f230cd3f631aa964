"""Pallas VMEM budget checker (DESIGN.md §14, budget from §3).

Every Pallas kernel in ``repro.kernels`` pipelines HBM blocks through
VMEM; the per-core budget is ~16 MB (DESIGN.md §3). A BlockSpec edit that
silently blows past it compiles fine in ``interpret=True`` CI and then
dies (or silently spills) on real hardware — exactly the class of
regression a static check should catch before merge.

Mechanism: the kernel modules all share the ``jax.experimental.pallas``
module object (``from jax.experimental import pallas as pl``), so the
checker temporarily swaps ``pallas_call`` for a recorder, runs each
module's *private impl* (``_fwd_impl``/``_bwd_impl``/…, plain functions —
the public entry points are jitted and would cache-skip the recorder)
under :func:`jax.eval_shape` at pinned serving-representative shapes, and
computes per-grid-step VMEM from the recorded BlockSpecs:

    footprint = 2 × (Σ in-block + Σ out-block bytes)   # double-buffered
              + Σ scratch bytes                        # persistent

Checks:

VMEM001  footprint over the §3 per-core budget.
VMEM002  footprint drifted from the committed per-kernel baseline
         (``vmem_baseline.json``) — intentional BlockSpec changes must
         regenerate it (``tools/lint_contracts.py --update-vmem-baseline``)
         so the diff is reviewed.
VMEM003  baseline/probe set out of sync: kernel missing from the
         baseline, or a stale baseline entry for a kernel that no longer
         exists.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os

import numpy as np

from repro.analysis.findings import Finding

# DESIGN.md §3: ~16 MB usable VMEM per TensorCore.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "vmem_baseline.json")

# Serving-representative probe shapes (match DESIGN.md §3's sizing table):
# d = head_dim, dv = value dim, m = R·P·D feature dim, T = chunk,
# bh/bk = q/kv head rows (GQA group 2), L = tokens, n = flat token count.
_D, _DV, _M, _T, _L = 128, 128, 384, 256, 512
_BH, _BK = 4, 2
_DEC_BK, _DEC_G = 8, 2
_N, _BLOCK = 512, 256


@dataclasses.dataclass(frozen=True)
class KernelFootprint:
    """Per-grid-step VMEM bytes for one recorded ``pallas_call``."""

    name: str            # "<module>.<kernel body fn>", e.g. "slay_scan._kernel"
    in_bytes: int        # Σ input block bytes (single copy)
    out_bytes: int       # Σ output block bytes (single copy)
    scratch_bytes: int   # Σ scratch_shapes bytes
    grid: tuple

    @property
    def total_bytes(self) -> int:
        # In/out blocks are double-buffered by the Pallas pipeline;
        # scratch is a single persistent allocation.
        return 2 * (self.in_bytes + self.out_bytes) + self.scratch_bytes


def _nbytes(shape, dtype) -> int:
    n = 1
    for dim in shape:
        n *= int(dim)
    return n * int(np.dtype(dtype).itemsize)


def _aslist(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _body_name(kernel) -> str:
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", repr(kernel))


@contextlib.contextmanager
def record_pallas_calls(records: list, module_label: str):
    """Swap ``jax.experimental.pallas.pallas_call`` for a recorder.

    The stub skips kernel tracing entirely and returns zeros of
    ``out_shape`` — enough for :func:`jax.eval_shape` to keep flowing
    through the surrounding impl code.
    """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def recorder(kernel, *, grid=None, in_specs=None, out_specs=None,
                 out_shape=None, scratch_shapes=None, **_kwargs):
        def run(*args):
            outs = _aslist(out_shape)
            in_bytes = 0
            for spec, arg in zip(_aslist(in_specs), args):
                if spec.block_shape is None:    # whole array in SMEM
                    continue
                in_bytes += _nbytes(spec.block_shape, arg.dtype)
            out_bytes = 0
            for spec, sds in zip(_aslist(out_specs), outs):
                out_bytes += _nbytes(spec.block_shape, sds.dtype)
            scratch_bytes = 0
            for ref in _aslist(scratch_shapes):
                scratch_bytes += _nbytes(ref.shape, ref.dtype)
            records.append(KernelFootprint(
                name=f"{module_label}.{_body_name(kernel)}",
                in_bytes=in_bytes, out_bytes=out_bytes,
                scratch_bytes=scratch_bytes,
                grid=tuple(grid) if grid is not None else ()))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in outs]
            if isinstance(out_shape, (list, tuple)):
                return tuple(zeros)
            return zeros[0]
        return run

    pl.pallas_call = recorder
    try:
        yield
    finally:
        pl.pallas_call = real


def _probe_all() -> list[KernelFootprint]:
    """Run every kernel module's impls under eval_shape; return records."""
    import jax
    import jax.numpy as jnp

    from repro.core.features import SlayFeatureConfig
    from repro.kernels import decode_step, feature_map, slay_fused, slay_scan

    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    cfg = SlayFeatureConfig(head_dim=_D)
    records: list[KernelFootprint] = []

    def run(label, impl, *args):
        with record_pallas_calls(records, label):
            jax.eval_shape(impl, *args)

    # slay_scan: feature-level chunked scan (fwd + two bwd kernels).
    st = slay_scan.ScanStatics(chunk_size=_T, delta=1e-6, interpret=True)
    qf, kf = sds((_BH, _L, _M), f32), sds((_BK, _L, _M), f32)
    v = sds((_BK, _L, _DV), f32)
    y, den = sds((_BH, _L, _DV), f32), sds((_BH, _L), f32)
    run("slay_scan", functools.partial(slay_scan._fwd_impl, st), qf, kf, v)
    run("slay_scan", functools.partial(slay_scan._bwd_impl, st),
        qf, kf, v, y, den, y)

    # feature_map: fused Ψ(u) (fwd + bwd).
    mst = feature_map._MapStatics(
        feat=slay_fused.statics_for(cfg, chunk_size=_T, delta=1e-6,
                                    interpret=True).feat,
        block_tokens=_BLOCK, interpret=True)
    u = sds((_N, _D), f32)
    anchors = sds((mst.feat.num_anchors, _D), f32)
    omegas = sds((mst.feat.num_prf, _D), f32)
    dpsi = sds((_N, _M), f32)
    run("feature_map", functools.partial(feature_map._fwd_impl, mst),
        u, anchors, omegas)
    run("feature_map", functools.partial(feature_map._bwd_impl, mst),
        u, anchors, omegas, dpsi)

    # slay_fused: megakernel (fwd + two bwd kernels) on raw q/k.
    fst = slay_fused.statics_for(cfg, chunk_size=_T, delta=1e-6,
                                 interpret=True)
    q, k = sds((_BH, _L, _D), f32), sds((_BK, _L, _D), f32)
    run("slay_fused", functools.partial(slay_fused._fwd_impl, fst),
        q, k, v, anchors, omegas)
    run("slay_fused", functools.partial(slay_fused._bwd_impl, fst),
        q, k, v, anchors, omegas, y, den, y)

    # decode_step: one-token serving step (plain + active-masked).
    dst = decode_step.DecodeStatics(delta=1e-6, interpret=True)
    dqf = sds((_DEC_BK * _DEC_G, _M), f32)
    dkf, dvv = sds((_DEC_BK, _M), f32), sds((_DEC_BK, _DV), f32)
    s = sds((_DEC_BK, _M, _DV), f32)
    z = sds((_DEC_BK, _M), f32)
    active = sds((_DEC_BK,), jnp.int32)
    run("decode_step", functools.partial(decode_step._decode_impl, dst),
        dqf, dkf, dvv, s, z)
    run("decode_step", functools.partial(decode_step._decode_masked, dst),
        dqf, dkf, dvv, s, z, active)

    return records


def probe_footprints() -> dict[str, KernelFootprint]:
    """Footprints keyed by kernel name; duplicates keep the max (a body
    reused at several sites is budgeted by its worst site)."""
    out: dict[str, KernelFootprint] = {}
    for rec in _probe_all():
        prev = out.get(rec.name)
        if prev is None or rec.total_bytes > prev.total_bytes:
            out[rec.name] = rec
    return out


def load_vmem_baseline(path: str = DEFAULT_BASELINE) -> dict[str, int]:
    with open(path) as fh:
        raw = json.load(fh)
    return {k: int(v) for k, v in raw.get("kernels", {}).items()}


def write_vmem_baseline(footprints: dict[str, KernelFootprint],
                        path: str = DEFAULT_BASELINE) -> None:
    payload = {
        "comment": "per-grid-step VMEM bytes (2x in/out blocks + scratch) "
                   "at the pinned probe shapes in analysis/vmem.py; "
                   "regenerate with tools/lint_contracts.py "
                   "--update-vmem-baseline",
        "budget_bytes": VMEM_BUDGET_BYTES,
        "kernels": {k: footprints[k].total_bytes
                    for k in sorted(footprints)},
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=False)
        fh.write("\n")


def check(footprints: dict[str, KernelFootprint] | None = None,
          baseline: dict[str, int] | None = None,
          budget: int = VMEM_BUDGET_BYTES) -> list[Finding]:
    """Run VMEM001/002/003 over probed footprints vs the baseline."""
    if footprints is None:
        footprints = probe_footprints()
    if baseline is None:
        baseline = (load_vmem_baseline()
                    if os.path.exists(DEFAULT_BASELINE) else {})
    findings = []
    label = "analysis/vmem"
    for name in sorted(footprints):
        fp = footprints[name]
        if fp.total_bytes > budget:
            findings.append(Finding(
                rule="VMEM001", path=label, line=0, symbol=name,
                message=(f"{fp.total_bytes/2**20:.2f} MiB per grid step "
                         f"exceeds the {budget/2**20:.0f} MiB §3 budget "
                         f"(in={fp.in_bytes}, out={fp.out_bytes}, "
                         f"scratch={fp.scratch_bytes})")))
        if name not in baseline:
            findings.append(Finding(
                rule="VMEM003", path=label, line=0, symbol=name,
                message=f"kernel missing from vmem_baseline.json "
                        f"(measured {fp.total_bytes} B) — regenerate "
                        f"the baseline"))
        elif baseline[name] != fp.total_bytes:
            findings.append(Finding(
                rule="VMEM002", path=label, line=0, symbol=name,
                message=(f"footprint {fp.total_bytes} B != baseline "
                         f"{baseline[name]} B — BlockSpec change; review "
                         f"and regenerate the baseline")))
    for name in sorted(set(baseline) - set(footprints)):
        findings.append(Finding(
            rule="VMEM003", path=label, line=0, symbol=name,
            message="stale vmem_baseline.json entry: kernel no longer "
                    "probed — regenerate the baseline"))
    return findings
