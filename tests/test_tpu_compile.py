"""Every Pallas kernel compiles for a TPU v5e at slayformer-124m widths.

Ahead-of-time compiles against a *described* ``v5e:2x2`` topology: the TPU
compiler is installed with jax, so it lowers each kernel for a chip that is
not attached and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, in-kernel relayouts Mosaic lacks, VMEM overruns). Nothing
runs. Interpret mode (the parity tests) checks none of that.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import decode_step, feature_map, slay_fused, slay_scan

CFG = get_config("slayformer-124m")
SCFG = CFG.slay_config()
D = CFG.resolved_head_dim            # 64
DV = D
M = SCFG.feature_dim                 # R·P·D = 3·8·16 = 384
T = CFG.chunk_size                   # 256
P, R_PRF = SCFG.num_anchors, SCFG.num_prf
BK = 32 * CFG.num_kv_heads           # a 32-slot serving pool, one row per kv head
BH = 2 * CFG.num_heads               # batch 2 of heads for the sequence kernels
L = 2 * T                            # two chunks: the carry crosses a boundary
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sum(fn):
    """Scalar loss over a kernel's output, for jax.grad."""
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32))


def _cases():
    def fused(q, k, v, a, w):
        return slay_fused.fused_causal_attention(q, k, v, a, w, SCFG,
                                                 chunk_size=T)

    def scan(q, k, v):
        return slay_scan.causal_linear_attention(q, k, v, chunk_size=T)

    def fmap(u, a, w):
        return feature_map.slay_feature_map(u, a, w, SCFG, block_tokens=T)

    raw = [((BH, L, D), BF), ((BH, L, D), BF), ((BH, L, DV), BF),
           ((P, D), jnp.float32), ((R_PRF, D), jnp.float32)]
    feats = [((BH, L, M), BF), ((BH, L, M), BF), ((BH, L, DV), BF)]
    tokens = [((4 * T, D), BF), ((P, D), jnp.float32),
              ((R_PRF, D), jnp.float32)]
    state = [((BK, M), BF), ((BK, M), BF), ((BK, DV), BF),
             ((BK, M, DV), jnp.float32), ((BK, M), jnp.float32)]
    return {
        "decode": (decode_step.decode_linear_attention, state),
        "decode_masked": (decode_step.decode_linear_attention,
                          state + [((BK,), jnp.int32)]),
        "fused_fwd": (fused, raw),
        "fused_grad": (jax.grad(_sum(fused), argnums=(0, 1, 2, 3, 4)), raw),
        "scan_fwd": (scan, feats),
        "scan_grad": (jax.grad(_sum(scan), argnums=(0, 1, 2)), feats),
        "feature_map_fwd": (fmap, tokens),
        "feature_map_grad": (jax.grad(_sum(fmap), argnums=(0, 1, 2)),
                             tokens),
    }


@pytest.mark.parametrize("kernel", ["decode", "decode_masked", "fused_fwd",
                                    "fused_grad", "scan_fwd", "scan_grad",
                                    "feature_map_fwd", "feature_map_grad"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _macro_hlo(one_chip, monkeypatch, cfg, slots, max_len, num_ticks=2):
    """Compiled text of the serving macro-step (``num_ticks`` ticks over a
    pool of ``slots`` slots of ``max_len`` rows, cache donated) for the
    described chip, and the pool's abstract cache."""
    from repro.kernels import ops
    from repro.models import api
    from repro.serving import engine

    # Whole-program compile for the described chip: take the TPU branch.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(api.abstract_params(cfg))
    cache = placed(jax.eval_shape(lambda: api.init_cache(cfg, slots,
                                                         max_len)))
    vectors = [jax.ShapeDtypeStruct((slots,), dt, sharding=one_chip)
               for dt in (jnp.int32, jnp.bool_, jnp.int32, jnp.int32,
                          jnp.int32, jnp.int32)]

    def macro(params, cache, *vectors):
        return engine._macro_decode(params, cache, *vectors, cfg=cfg,
                                    num_ticks=num_ticks, temperature=0.0,
                                    seed=0)

    text = jax.jit(macro, donate_argnums=(1,)).lower(
        params, cache, *vectors).compile().as_text()
    return text, cache


def test_macro_decode_moves_pool_state_only_in_kernel(one_chip, monkeypatch):
    """The serving macro-step (K = 2 ticks, 8 slots, 2 layers) updates the
    layer-stacked SLAY state in place: no copy, dynamic slice or dynamic
    update, fused or not, produces the stack or one layer of it, in the
    model's layout or the kernel's view. The decode kernel is the only op
    that touches it."""
    from repro.analysis import hlo

    nl, slots, hkv = 2, 8, CFG.num_kv_heads
    cfg = get_config("slayformer-124m", num_layers=nl)
    text, _ = _macro_hlo(one_chip, monkeypatch, cfg, slots, 2048)
    assert "tpu_custom_call" in text
    rows = slots * hkv
    state = {f"f32[{','.join(map(str, s))}]" for s in (
        (nl, slots, hkv, M, DV), (1, slots, hkv, M, DV), (slots, hkv, M, DV),
        (nl, rows, M, DV), (1, rows, M, DV), (rows, M, DV),
        (nl, rows, DV, M), (1, rows, DV, M), (rows, DV, M))}
    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    bad = [i.text[:160] for i in hlo.parse_hlo(text).instructions
           if i.shape.split("{")[0] in state
           and (hlo.base_opcode(i.opcode) in moves
                or (i.opcode == "fusion" and any(m in i.name for m in moves)))]
    assert not bad, "\n".join(bad)


def test_mixed_macro_decode_updates_state_stacks_in_place(one_chip,
                                                          monkeypatch):
    """granite-4.0-h-micro's widths over six layers [M, M, A, M, A, M]
    (K = 2 ticks, the cell's 64 slots of 2048-row rings): the serving
    macro-step updates the SSM state stack and the K and V ring stacks in
    place. No copy produces either stack, nor one layer of it: each
    layer's body reads and writes its own entry of its kind's stack,
    riding the loop carry. (Under ``lax.cond`` between the two bodies,
    the branch that passes a stack through copies it whole.) No select
    produces a ring stack, a layer of it or a ring: the new K/V row is
    written for each live slot alone."""
    import json

    from bench import serving, spec
    from repro.analysis import hlo

    with open(spec.config_path("granite-4.0-h-micro",
                               spec.load_benchmark())) as f:
        cfg = json.load(f)
    cfg["arch"].update(num_layers=6, layer_types="mamba,mamba,attention,"
                       "mamba,attention,mamba")
    cfg = serving.arch_config(cfg)
    text, cache = _macro_hlo(one_chip, monkeypatch, cfg, 64, 2048)
    names = {jnp.dtype(jnp.float32): "f32", jnp.dtype(jnp.bfloat16): "bf16"}
    stacks = set()
    for leaf in (cache.ssm.h, cache.attn.k, cache.attn.v):
        s = leaf.shape
        for shape in (s, (1, *s[1:]), s[1:]):
            stacks.add(f"{names[leaf.dtype]}[{','.join(map(str, shape))}]")
    copies = [i.text[:160] for i in hlo.parse_hlo(text).instructions
              if i.shape.split("{")[0] in stacks
              and (hlo.base_opcode(i.opcode) == "copy"
                   or (i.opcode == "fusion" and "copy" in i.name))]
    assert not copies, "\n".join(copies)
    # Nor does a select over a whole ring write the new K/V row: each
    # live slot writes its one row.
    rings = set()
    for leaf in (cache.attn.k, cache.attn.v):
        s = leaf.shape
        for shape in (s, (1, *s[1:]), s[1:]):
            rings.add(f"bf16[{','.join(map(str, shape))}]")
    ops, _, _ = _ops(text)
    root_of = {o["comp"]: o for o in ops if o["root"]}
    selects = [o["name"] for o in ops if o["shape"] in rings
               and ("select" in o["name"] or o["opcode"] == "select"
                    or (o["opcode"] == "fusion"
                        and root_of[o["calls"][0]]["opcode"] == "select"))]
    assert not selects, selects


_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+?\[[^\]]*\])\S*\s+"
                 r"([\w\-]+)\(([^)]*)\)")


def _ops(text):
    """The array-valued instructions of compiled HLO text, each with its
    computation: (name, shape, opcode, operand names, root flag, called
    computations), and the set of fused computations. Shapes drop their
    layout: ``bf16[2,32,2048,12,64]``."""
    ops, comp, entry = [], None, None
    for line in text.splitlines():
        if comp is None:
            m = _HEADER.match(line)
            if m and not line[0].isspace():
                comp = m.group(2)
                entry = comp if m.group(1) else entry
            continue
        if line.strip() == "}":
            comp = None
            continue
        m = _OP.match(line)
        if m:
            ops.append(dict(
                comp=comp, root=bool(m.group(1)), name=m.group(2),
                shape=m.group(3), opcode=m.group(4),
                args=re.findall(r"%([\w.\-]+)", m.group(5)),
                calls=re.findall(r"calls=%([\w.\-]+)", line)))
    fused = {c for o in ops if o["opcode"] == "fusion" for c in o["calls"]}
    return ops, fused, entry


@pytest.mark.parametrize("num_ticks", [2, 4])
def test_macro_decode_writes_one_ring_row_per_slot(one_chip, monkeypatch,
                                                   num_ticks):
    """The softmax macro-step at slayformer-124m-softmax widths (2 layers,
    32 slots of 2048-row rings) carries the K and V ring stacks through
    the layer scan and writes one row per slot in place. Inside the tick
    and layer loops no copy, select or dynamic slice produces a stack
    (nl, B, S, Hkv, dh), one layer of it or one ring (fused or not: a
    dynamic slice may only feed the attention inside its fusion), and
    the writes into each stack are one (Hkv, dh) row per slot. At the
    program's edge at most one relayout copy per stack each way."""
    nl = 2
    cfg = get_config("slayformer-124m", attn_kind="softmax", num_layers=nl)
    text, cache = _macro_hlo(one_chip, monkeypatch, cfg, 32, 2048,
                             num_ticks)
    ring = cache.attn.k.shape[1:]
    ops, fused, entry = _ops(text)
    stack = "bf16[{}]".format(",".join(map(str, (nl, *ring))))
    shapes = {"bf16[{}]".format(",".join(map(str, s)))
              for s in ((nl, *ring), (1, *ring), ring)}
    by_name = {o["name"]: o for o in ops}
    root_of = {o["comp"]: o for o in ops if o["root"]}
    moves = ("copy", "select", "dynamic-slice")

    def made_by(o):
        if o["opcode"] == "fusion":
            return root_of[o["calls"][0]]["opcode"]
        return o["opcode"]

    bad = [o["name"] for o in ops
           if o["comp"] not in fused and o["comp"] != entry
           and o["shape"] in shapes and made_by(o) in moves]
    assert not bad, bad
    row = int(np.prod(ring[2:]))        # one slot's (Hkv, dh) row
    writes = [o for o in ops if o["shape"] == stack
              and o["opcode"] in ("scatter", "dynamic-update-slice")]
    sizes = []
    for o in writes:
        upd = by_name[o["args"][2 if o["opcode"] == "scatter" else 1]]
        dims = upd["shape"].split("[")[1].rstrip("]").split(",")
        sizes.append(int(np.prod([int(d) for d in dims])))
    # K and V: a dynamic update of one row for each slot, or a scatter of
    # one row per slot.
    assert sorted(sizes) in ([row] * 2 * ring[0],
                             [row * ring[0]] * 2), sizes
    edge = [o["name"] for o in ops if o["comp"] == entry
            and o["shape"] == stack and made_by(o) == "copy"]
    assert len(edge) <= 4, edge
