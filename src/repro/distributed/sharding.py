"""Logical-axis sharding: GSPMD rules with divisibility-aware fallback.

Model code annotates parameters with *logical* axis names (see
``repro.models.layers.ParamSpec``); this module maps them to mesh axes:

    batch    -> (pod, data)      activations' batch dim (DP across pods too)
    embed    -> data             FSDP: params/opt-state sharded over data
    heads    -> model            TP over attention heads
    kv_heads -> model            TP over kv heads (falls back when Hkv < mesh)
    mlp      -> model            TP over FFN hidden
    vocab    -> model            TP over embedding/unembedding rows
    experts  -> model            EP over MoE experts
    layers   -> None             scan axis, never sharded
    seq      -> model            SP for long-context activations
    slots    -> data             serving slot-pool dim (DESIGN.md §8)

The fallback rule: if a tensor dim is not divisible by the mesh-axis size
(e.g. granite's single KV head over 16-way model parallelism), the rule
engine *drops the mesh axis* (replicates) rather than failing — recorded so
the dry-run report can show which dims replicated.

Rules are data (a dataclass), so perf iterations can swap whole schemes
(§Perf beyond-paper experiments) without touching model code.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, or None).

    The ``act_*`` entries govern *activation* constraints
    (``with_sharding_constraint`` inside the model forward):

        act_batch   batch dim of every activation          -> DP
        act_embed   residual-stream d_model dim            -> None (replicated)
        act_heads   per-head dims of q/k/v/attn-out        -> TP
        act_mlp     FFN hidden dim                         -> TP
        act_seq     sequence dim (sequence parallelism)    -> None at 4k

    Megatron-style defaults: residual replicated over `model`, heads/FFN
    sharded over `model` — XLA then inserts exactly one all-reduce after
    the attention-out / FFN-down contractions instead of the d-sharded
    residual + per-op resharding it otherwise invents.
    """

    batch: tuple[str, ...] | str | None = ("pod", "data")
    embed: tuple[str, ...] | str | None = "data"
    heads: tuple[str, ...] | str | None = "model"
    kv_heads: tuple[str, ...] | str | None = "model"
    mlp: tuple[str, ...] | str | None = "model"
    vocab: tuple[str, ...] | str | None = "model"
    experts: tuple[str, ...] | str | None = "model"
    seq: tuple[str, ...] | str | None = None
    layers: tuple[str, ...] | str | None = None
    # Serving slot pool: the slot dim of the pooled decode cache and of the
    # engine's per-slot control vectors shards over `data` (DESIGN.md §8).
    slots: tuple[str, ...] | str | None = "data"
    act_batch: tuple[str, ...] | str | None = ("pod", "data")
    act_embed: tuple[str, ...] | str | None = None
    act_heads: tuple[str, ...] | str | None = "model"
    act_mlp: tuple[str, ...] | str | None = "model"
    act_seq: tuple[str, ...] | str | None = None
    act_vocab: tuple[str, ...] | str | None = "model"

    def lookup(self, logical: str | None):
        if logical is None:
            return None
        return getattr(self, logical)


DEFAULT_RULES = ShardingRules()


# ---------------------------------------------------------------------------
# Activation-sharding context (MaxText-style logical constraints)
# ---------------------------------------------------------------------------

_ACTIVATION_CTX: list = []   # stack of (mesh, rules)


class activation_sharding:
    """Context manager installing (mesh, rules) for ``constrain`` calls
    inside model code. No-op when not entered (CPU unit tests)."""

    def __init__(self, mesh: Mesh, rules: ShardingRules = DEFAULT_RULES):
        self.pair = (mesh, rules)

    def __enter__(self):
        _ACTIVATION_CTX.append(self.pair)
        return self

    def __exit__(self, *exc):
        _ACTIVATION_CTX.pop()
        return False


def constrain(x, logical_axes: tuple):
    """with_sharding_constraint by logical activation axes; identity when no
    activation_sharding context is installed. Divisibility-checked the same
    way as parameters (drop-axis fallback)."""
    if not _ACTIVATION_CTX:
        return x
    mesh, rules = _ACTIVATION_CTX[-1]
    spec = partition_spec(mesh, rules, tuple(x.shape), tuple(logical_axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


_SLOT_POOL_CTX: list = []    # stack of (mesh, slot axes)


def in_slot_pool(fn, mesh: Mesh, axes: tuple[str, ...]):
    """Wrap ``fn`` so that, while it runs (jit traces it by running it),
    :func:`current_slot_pool` names the mesh and the axes the serving slot
    pool shards over. A ``pallas_call`` is opaque to GSPMD, so a kernel on
    the slot dim (``kernels.ops.decode_linear_step``) reads this to run
    once per shard under ``shard_map`` instead of gathering the pool —
    which keeps the decode step free of collectives (DESIGN.md §8)."""
    def wrapped(*args, **kwargs):
        _SLOT_POOL_CTX.append((mesh, tuple(axes)))
        try:
            return fn(*args, **kwargs)
        finally:
            _SLOT_POOL_CTX.pop()
    return wrapped


def current_slot_pool():
    """(mesh, slot axes) of the pool being traced, or None outside one."""
    return _SLOT_POOL_CTX[-1] if _SLOT_POOL_CTX else None


def _mesh_axes_present(mesh: Mesh, axes) -> tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if a in mesh.shape)


def partition_spec(mesh: Mesh, rules: ShardingRules, shape: tuple[int, ...],
                   logical_axes: tuple[str | None, ...],
                   fallback_log: list | None = None) -> P:
    """Build a PartitionSpec honoring divisibility (drop-axis fallback)."""
    if len(shape) != len(logical_axes):
        raise ValueError(f"rank mismatch: {shape} vs {logical_axes}")
    spec = []
    used: set[str] = set()
    for dim, logical in zip(shape, logical_axes):
        axes = _mesh_axes_present(mesh, rules.lookup(logical))
        # Drop mesh axes already used by an earlier dim of this tensor.
        axes = tuple(a for a in axes if a not in used)
        total = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
            if axes else 1
        while axes and dim % total:
            dropped = axes[-1]
            axes = axes[:-1]
            total = int(np.prod([mesh.shape[a] for a in axes],
                                dtype=np.int64)) if axes else 1
            if fallback_log is not None:
                fallback_log.append((logical, dim, dropped))
        used.update(axes)
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(tuple(axes))
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def logical_to_sharding(mesh: Mesh, rules: ShardingRules, abstract, axes,
                        fallback_log: list | None = None):
    """Map a pytree of (ShapeDtypeStruct|Array) + logical-axes pytree to
    NamedShardings."""
    def one(x, ax):
        return NamedSharding(mesh, partition_spec(
            mesh, rules, tuple(x.shape), tuple(ax), fallback_log))
    return jax.tree.map(one, abstract, axes,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(a, (str, type(None))) for a in x))


def shard_params(mesh: Mesh, rules: ShardingRules, params, axes):
    """Device_put a realized param tree onto the mesh per the rules."""
    sh = logical_to_sharding(mesh, rules, params, axes)
    return jax.tree.map(jax.device_put, params, sh)


def batch_sharding(mesh: Mesh, rules: ShardingRules = DEFAULT_RULES,
                   *, extra_rank: int = 1,
                   batch_size: int | None = None) -> NamedSharding:
    """Sharding for (B, ...) input batches: batch dim over (pod, data).

    When ``batch_size`` is given, axes that do not divide it are dropped
    (innermost first) — e.g. the long_500k cell's global_batch=1 replicates
    rather than failing to lower."""
    axes = _mesh_axes_present(mesh, rules.batch)
    if batch_size is not None:
        total = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
            if axes else 1
        while axes and batch_size % total:
            axes = axes[:-1]
            total = int(np.prod([mesh.shape[a] for a in axes],
                                dtype=np.int64)) if axes else 1
    ax = axes[0] if len(axes) == 1 else (tuple(axes) if axes else None)
    return NamedSharding(mesh, P(ax))


def serving_param_rules(rules: ShardingRules = DEFAULT_RULES
                        ) -> ShardingRules:
    """Serving-time parameter rules: replicate over the slot axes.

    Training shards params over ``data`` (FSDP, ``embed -> data``); at
    decode the ``data`` axis carries slot parallelism instead, and an
    FSDP-sharded param tree would force a weight all-gather inside every
    decode tick. Serving therefore replicates params over the slot axes
    (keeping TP axes intact) — the enabler for the §8 zero-collective
    decode hot loop contract.
    """
    slot_axes = rules.slots if isinstance(rules.slots, tuple) else \
        (rules.slots,) if rules.slots else ()

    def strip(entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a not in slot_axes)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    # Strip the slot axes from *every* rule (custom rule sets may map any
    # logical axis to `data`), except `slots` itself — that one IS the
    # slot-pool sharding the engine resolves separately.
    return dataclasses.replace(rules, **{
        f.name: strip(getattr(rules, f.name))
        for f in dataclasses.fields(rules) if f.name != "slots"})


def pool_slot_axes(mesh: Mesh, rules: ShardingRules, num_slots: int,
                   requested: int = 0,
                   fallback_log: list | None = None
                   ) -> tuple[tuple[str, ...], int]:
    """Resolve the mesh axes the serving slot pool shards over.

    ``requested`` is ``ServingConfig.slot_shards``: 0 = auto (the whole
    slot mesh axis, normally ``data``), 1 = force a single shard
    (replicate), N > 1 = demand exactly N-way sharding (raises if the mesh
    slot axes don't multiply to N — a config/mesh mismatch, not a
    fallback). Slot->shard ownership is static: GSPMD splits the slot dim
    into contiguous blocks, so shard k owns slots
    [k*S/N, (k+1)*S/N) for the engine's lifetime.

    Divisibility fallback: when ``num_slots`` is not divisible by the
    slot-axis size the axis is dropped (pool replicates) and the drop is
    recorded in ``fallback_log`` as ``("slots", num_slots, axis)`` — the
    same contract as :func:`partition_spec`'s rule engine.

    Returns ``(axes, shard_count)``; ``axes`` is ``()`` when replicated.
    """
    axes = _mesh_axes_present(mesh, rules.slots)
    size = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
        if axes else 1
    if requested > 1 and requested != size:
        raise ValueError(
            f"slot_shards={requested} but mesh slot axes {axes} have size "
            f"{size}; build the mesh to match (e.g. make_serving_mesh)")
    if requested == 1 or not axes or size == 1:
        return (), 1
    while axes and num_slots % size:
        dropped = axes[-1]
        axes = axes[:-1]
        size = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
            if axes else 1
        if fallback_log is not None:
            fallback_log.append(("slots", num_slots, dropped))
    return axes, size


def _axis_entry(axes: tuple[str, ...]):
    """Collapse an axis tuple to a PartitionSpec entry."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def serving_cache_sharding(mesh: Mesh, rules: ShardingRules, abstract, *,
                           num_slots: int | None = None,
                           num_pages: int | None = None,
                           slot_shards: int = 0,
                           fallback_log: list | None = None):
    """Slot-stable, slot-sharded decode-cache shardings for the pool.

    Derived from leaf *shapes* only (never from which slots are live), with
    the pool's slot dim fixed for the engine's lifetime — so admission and
    eviction (single-slot overwrites via ``api.reset_slot``/``write_slot``)
    keep every leaf's sharding bit-identical and never trigger a reshard or
    a host round-trip. The engine jits its decode/slot ops with these as
    both in- and out-shardings (cache donated), making that contract
    explicit to XLA.

    The slot dim — dim 1 of every stacked ``(nl, S, ...)`` leaf and dim 0
    of the ``(S,)`` per-slot ``pos`` vector — shards over ``rules.slots``
    (the ``data`` mesh axis; DESIGN.md §8), so each data shard owns a
    contiguous static block of slots end-to-end through the decode scan.
    Head-like dims keep the TP heuristic of :func:`cache_sharding`.
    ``num_slots``/``slot_shards``/``fallback_log`` follow
    :func:`pool_slot_axes`; ``num_slots`` is inferred from the leaves when
    omitted.

    Paged pool (DESIGN.md §11): pass ``num_pages`` so the page dim —
    dim 1 of the ``(nl, P, page, Hkv, dh)`` ring leaves — shards over the
    same slot axes (pages are allocated shard-block-aligned with their
    owning slots, so this keeps every page on its owner's shard). A
    ``DecodeCache.pages`` PageState is sharded explicitly: table (S, Lp)
    by slot dim 0, owner vectors (P,) by page dim 0.
    """
    pstate = getattr(abstract, "pages", None)
    base = abstract._replace(pages=None) if pstate is not None else abstract
    if num_slots is None:
        for x in jax.tree.leaves(base):
            if len(x.shape) >= 2:
                num_slots = int(x.shape[1])
                break
        else:                         # pragma: no cover — degenerate tree
            num_slots = 1
    saxes, _ = pool_slot_axes(mesh, rules, num_slots, slot_shards,
                              fallback_log)
    sax = _axis_entry(saxes)
    maxes = tuple(a for a in _mesh_axes_present(mesh, rules.heads)
                  if a not in saxes)
    msize = int(np.prod([mesh.shape[a] for a in maxes], dtype=np.int64)) \
        if maxes else 1
    mx = _axis_entry(maxes)

    def one(x):
        shape = tuple(x.shape)
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        if len(shape) == 1:           # per-slot pos vector
            return NamedSharding(
                mesh, P(sax) if shape[0] == num_slots else P())
        spec: list = [None] * len(shape)
        if shape[1] == num_slots or (num_pages is not None
                                     and shape[1] == num_pages):
            spec[1] = sax
        # Shard the head-like axis (dim 2 for state/ssm, dim 3 for kv ring).
        for cand in (3, 2):
            if len(shape) > cand and shape[cand] % max(msize, 1) == 0 \
                    and msize > 1 and shape[cand] >= msize:
                spec[cand] = mx
                break
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    tree = jax.tree.map(one, base)
    if pstate is not None:
        tree = tree._replace(pages=page_state_sharding(mesh, sax, pstate))
    return tree


def page_state_sharding(mesh: Mesh, sax, pstate):
    """Shardings for a PageState pytree: every child shards its leading
    dim over the slot axes (table rows are slots; owner vectors are
    pages, block-aligned with their owning shard)."""
    cls = type(pstate)
    return cls(NamedSharding(mesh, P(sax)), NamedSharding(mesh, P(sax)),
               NamedSharding(mesh, P(sax)), shards=pstate.shards)


def serving_vector_sharding(mesh: Mesh,
                            rules: ShardingRules = DEFAULT_RULES, *,
                            num_slots: int,
                            slot_shards: int = 0, leading: int = 0,
                            fallback_log: list | None = None
                            ) -> NamedSharding:
    """Slot sharding for the engine's per-slot control vectors.

    The macro-step decode signature carries ``(num_slots,)``-shaped
    int32/bool vectors — last token, active mask, request ids, per-slot
    generation counts / EOS ids / budgets — plus the
    ``(K, num_slots)``-shaped token/emitted buffers it returns
    (``leading=1``). Every one of them carries the *same* slot sharding as
    the pool cache: each data shard reads exactly its own slots' control
    state and writes exactly its own slots' tokens, which is what keeps the
    K-tick decode scan free of cross-shard collectives (DESIGN.md §8).
    When the pool replicates (divisibility fallback, or a mesh without
    slot axes) these replicate too — shardings always move in lockstep
    with the cache, which is why ``num_slots`` is required: the
    divisibility decision must be made from the same inputs here and in
    :func:`serving_cache_sharding`.
    """
    saxes, _ = pool_slot_axes(mesh, rules, num_slots, slot_shards,
                              fallback_log)
    return NamedSharding(mesh, P(*([None] * leading), _axis_entry(saxes)))


def cache_sharding(mesh: Mesh, rules: ShardingRules, abstract):
    """Decode caches: shard the batch dim (first non-layer dim) over
    (pod, data) and head-like dims heuristically over model.

    Cache layouts (stacked layers first, then batch):
        kv ring:      (nl, B, S, Hkv, dh)
        linear state: (nl, B, Hkv, m, dv) / (nl, B, Hkv, m)
        ssm state:    (nl, B, nh, hd, ds); conv (nl, B, W-1, C)
    """
    baxes = _mesh_axes_present(mesh, rules.batch)
    bax = baxes[0] if len(baxes) == 1 else (tuple(baxes) if baxes else None)
    maxes = _mesh_axes_present(mesh, rules.heads)
    msize = int(np.prod([mesh.shape[a] for a in maxes], dtype=np.int64)) \
        if maxes else 1
    mx = maxes[0] if len(maxes) == 1 else (tuple(maxes) if maxes else None)

    def one(x):
        shape = tuple(x.shape)
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        if len(shape) == 1:  # per-layer scalars (pos)
            return NamedSharding(mesh, P())
        bsize = int(np.prod([mesh.shape[a] for a in baxes],
                            dtype=np.int64)) if baxes else 1
        spec: list = [None] * len(shape)
        if shape[1] % max(bsize, 1) == 0 and bsize > 1:
            spec[1] = bax
        # Shard the head-like axis (dim 2 for state/ssm, dim 3 for kv ring).
        for cand in (3, 2):
            if len(shape) > cand and shape[cand] % max(msize, 1) == 0 \
                    and msize > 1 and shape[cand] >= msize:
                spec[cand] = mx
                break
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, abstract)
