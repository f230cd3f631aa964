"""Architecture + shape-cell configuration system.

Every assigned architecture is an :class:`ArchConfig` (one module per arch in
this package, exposing ``CONFIG`` and ``smoke_config()``). Shape cells follow
the assignment:

    train_4k     seq 4096,   batch 256   -> train_step
    prefill_32k  seq 32768,  batch 32    -> prefill (full forward, no loss)
    decode_32k   seq 32768,  batch 128   -> serve_step (1 token, 32k cache)
    long_500k    seq 524288, batch 1     -> serve_step (sub-quadratic only)

``input_specs`` returns jax.ShapeDtypeStruct stand-ins — no allocation — for
the dry-run's .lower().
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.features import SlayFeatureConfig
from repro.core.slay import AttentionSpec


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # decoder | moe | ssm | hybrid | encdec
    num_layers: int
    d_model: int
    vocab_size: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    # Feature flags
    qk_norm: bool = False
    attn_logit_softcap: float = 0.0   # gemma2 attention softcap
    final_logit_softcap: float = 0.0  # gemma2 output softcap
    local_window: int = 0             # sliding window for local layers
    local_global_period: int = 0      # every Nth layer global (0 = all global)
    gated_mlp: bool = True
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 2
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1
    ssm_conv_width: int = 4
    # Encoder-decoder (whisper)
    enc_layers: int = 0
    enc_seq: int = 0                  # precomputed frame embeddings length
    # Modality frontend stub: "" | "audio" | "vision"
    frontend: str = ""
    num_patches: int = 0              # VLM: patch-embedding prefix length
    # Attention backend ("slay" = the paper's technique; "softmax" baseline)
    attn_kind: str = "slay"
    slay_anchors: int = 8
    slay_prf: int = 16
    slay_quad_nodes: int = 3
    chunk_size: int = 256
    # Mixed layer stack: one mixer kind per layer, "mamba" (a Mamba-2 SSD
    # block) or "attention" (exact GQA over a KV ring); () = the family's
    # one kind in every layer. The keys below it belong to the mixed stack
    # (granite-4.0-h's muP multipliers, NoPE, its norm eps) and keep their
    # defaults elsewhere.
    layer_types: tuple[str, ...] = ()
    embedding_multiplier: float = 1.0  # embeddings x this
    residual_multiplier: float = 1.0   # each branch x this before its add
    logits_scaling: float = 1.0        # logits / this
    attention_multiplier: float = 0.0  # score scale; 0 = 1/sqrt(head_dim)
    position_embedding: str = "rope"   # rope | nope
    norm_eps: float = 1e-6             # RMSNorm and Mamba-2 gated-norm eps
    # Pallas attention kernels (trainable — the kernels carry custom VJPs).
    # use_pallas dispatches the compiled kernels on TPU (jnp reference
    # elsewhere — kernels.ops picks by platform, so the CPU tests run the
    # reference); fuse_attention_features selects the end-to-end
    # megakernel over the two-dispatch feature→scan pipeline.
    use_pallas: bool = True
    fuse_attention_features: bool = True
    # Numerics
    dtype: str = "bfloat16"
    # Source provenance (public-literature citation)
    source: str = ""

    def __post_init__(self):
        # The config is a static jit argument, so it must hash: a list
        # becomes a tuple, and a string (how a configuration file whose
        # values are hashed, as bench/weights.py hashes them, gives it)
        # is split at its commas. A stack cut in depth keeps the first
        # num_layers layers of the pattern.
        kinds = self.layer_types
        if isinstance(kinds, str):
            kinds = kinds.split(",") if kinds else ()
        if kinds and len(kinds) < self.num_layers:
            raise ValueError(f"layer_types has {len(kinds)} entries for "
                             f"{self.num_layers} layers")
        object.__setattr__(self, "layer_types",
                           tuple(kinds[:self.num_layers]))
        if set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types holds only 'mamba' and "
                             f"'attention', got {self.layer_types!r}")
        if self.position_embedding not in ("rope", "nope"):
            raise ValueError(f"position_embedding is rope or nope, got "
                             f"{self.position_embedding!r}")
        mixed_only = dict(embedding_multiplier=1.0, residual_multiplier=1.0,
                          logits_scaling=1.0, attention_multiplier=0.0,
                          position_embedding="rope", norm_eps=1e-6)
        if not self.layer_types:
            off = [k for k, v in mixed_only.items() if getattr(self, k) != v]
            if off:
                raise ValueError(f"{off} apply to a mixed stack only: set "
                                 f"layer_types")
        elif self.family != "decoder" or self.moe_experts or self.frontend \
                or self.local_window or self.attn_kind != "softmax":
            raise ValueError("a mixed stack is a dense decoder of Mamba-2 "
                             "and softmax attention layers")

    @property
    def activation_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    def slay_config(self) -> SlayFeatureConfig:
        return SlayFeatureConfig(
            head_dim=self.resolved_head_dim, num_anchors=self.slay_anchors,
            num_prf=self.slay_prf, num_quad_nodes=self.slay_quad_nodes)

    def attention_spec(self, *, local: bool = False) -> AttentionSpec:
        """The AttentionSpec for a (global|local) layer under this config."""
        if self.layer_types:
            return AttentionSpec(kind="softmax", chunk_size=self.chunk_size,
                                 scale=self.attention_multiplier)
        if local and self.local_window:
            return AttentionSpec(kind="softmax", window=self.local_window,
                                 logit_softcap=self.attn_logit_softcap,
                                 chunk_size=self.chunk_size)
        if self.attn_kind == "slay":
            return AttentionSpec(kind="slay", slay=self.slay_config(),
                                 chunk_size=self.chunk_size,
                                 use_pallas=self.use_pallas,
                                 fuse_features=self.fuse_attention_features)
        return AttentionSpec(kind=self.attn_kind,
                             logit_softcap=self.attn_logit_softcap,
                             chunk_size=self.chunk_size,
                             slay=self.slay_config()
                             if self.attn_kind == "slay" else None)

    @property
    def param_count_dense(self) -> int:
        """Approximate parameter count N (for MODEL_FLOPS = 6 N D)."""
        d, L = self.d_model, self.num_layers
        dh = self.resolved_head_dim
        n = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            di = self.ssm_expand * d
            per = d * (2 * di + 2 * self.ssm_ngroups * self.ssm_state
                       + di // self.ssm_head_dim) + di * d
            return n + L * per
        attn = d * dh * (self.num_heads + 2 * self.num_kv_heads) \
            + self.num_heads * dh * d
        mlp = d * self.d_ff * (3 if self.gated_mlp else 2)
        if self.moe_experts:
            mlp = mlp * self.moe_experts + d * self.moe_experts
        per = attn + mlp
        if self.family == "hybrid":
            di = self.ssm_expand * d
            per += d * (2 * di + 2 * self.ssm_ngroups * self.ssm_state
                        + di // self.ssm_head_dim) + di * d
        total = n + L * per
        if self.family == "encdec":
            total += self.enc_layers * (attn + mlp) + L * attn  # cross-attn
        return total

    @property
    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if not self.moe_experts:
            return self.param_count_dense
        d, L = self.d_model, self.num_layers
        dh = self.resolved_head_dim
        attn = d * dh * (self.num_heads + 2 * self.num_kv_heads) \
            + self.num_heads * dh * d
        mlp_active = d * self.d_ff * 3 * self.moe_top_k + d * self.moe_experts
        return self.vocab_size * d + L * (attn + mlp_active)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching engine knobs (see repro.serving.engine).

    The pool has ``num_slots`` decode slots, each with ``max_len`` context
    capacity (KV ring size; the constant-state path is length-independent).
    Prompts are absorbed ``prefill_chunk`` tokens per engine tick so long
    prompts cannot stall the decode pool; ``decode_ticks_per_prefill``
    decode ticks run between consecutive prefill chunks when both kinds of
    work are pending (1 = strict alternation).

    ``macro_ticks`` (K) is the decode macro-step: the engine wraps K decode
    ticks in one jitted ``lax.scan`` dispatch with fused on-device sampling
    and pulls a (K, num_slots) token buffer to host once per dispatch
    instead of a logits matrix per tick. Token streams are byte-identical
    for any K (sampling is keyed per (seed, rid, token-index)); larger K
    trades admission/streaming granularity (up to K ticks) for ~K× fewer
    host syncs and dispatches. K=1 recovers per-tick behavior.

    ``prefill_buckets`` pads the non-chunkable prefill fallback to pow-2
    length buckets (>= ``prefill_bucket_min``, capped at ``max_len``) so
    it compiles once per bucket instead of once per distinct prompt
    length; masked out exactly via ``true_len``. Only modality frontends
    still take this fallback — every decoder-only config (ssm/hybrid and
    the exact yat kinds included) prefills chunk-by-chunk since
    DESIGN.md §9.

    ``slot_shards`` partitions the slot pool over the mesh ``data`` axis
    (DESIGN.md §8): 0 = auto (shard over the whole data axis when
    ``num_slots`` is divisible by it, else replicate — recorded like the
    rule-engine divisibility fallback), 1 = force a single shard
    (replicated pool), N > 1 = demand exactly N-way sharding (the engine
    raises on a mesh whose data axis is not N). Token streams are
    byte-identical across any value — sampling is keyed on
    (seed, rid, token-index), never on slot or shard placement.

    Fault model (DESIGN.md §10): ``overload_policy`` picks what a full
    admission queue (``max_queue`` > 0) does with new work —

    * ``"reject_new"``: ``Scheduler.submit`` raises
      :class:`repro.serving.engine.QueueFullError` (typed, carries
      ``queue_depth``/``max_queue``) and the caller keeps the request;
    * ``"shed_oldest"``: the longest-waiting queued request is shed
      (``finish_reason="shed"``) to make room — freshest work wins;
    * ``"queue_wait"``: admission never rejects, but any request still
      queued ``queue_wait_ticks`` ticks after its arrival is shed — a
      queue-wait deadline that bounds staleness instead of depth.

    ``fault_guard`` enables the per-slot NaN/Inf finiteness lane inside
    the jitted decode macro-step (one extra (K, num_slots) bool plane in
    the token buffer the host already pulls — no new host syncs); on a
    detected fault the engine quarantines the slot (``reset_slot``) and
    re-admits the request up to ``fault_retries`` times before failing it
    with ``finish_reason="fault"``.

    Paged slot memory (DESIGN.md §11): ``page_size > 0`` splits the KV
    ring leaves of configs that support paging (non-windowed quadratic
    rings) into shared physical pages; admission allocates
    ``ceil((prompt + max_new) / page_size)`` pages, so short requests
    stop paying ``max_len``. ``num_pages`` sizes the physical pool
    (0 = ``num_slots * max_len / page_size``, i.e. no memory saving but
    full paging mechanics — set it lower to overcommit). Constant-state
    configs ignore both. ``prefix_cache_bytes > 0`` enables the
    content-addressed prefix cache: admission seeds a slot from the
    longest cached prompt-prefix snapshot and chunk-prefills only the
    suffix (LRU-evicted under this byte budget; streams stay
    byte-identical cached-vs-cold).

    Speculative decoding (DESIGN.md §13): ``speculative=True`` turns each
    of the K macro-ticks into a draft-verify *round* — the linear SLAY
    draft proposes ``spec_gamma`` tokens per slot, the exact verifier
    scores all of them in one ``verify_chunk`` dispatch, and standard
    accept/resample correction keeps the output distribution exactly the
    verifier's (greedy streams byte-identical to plain greedy decode).
    Requires a verifier config with ``api.supports_speculative`` (a
    non-windowed exact quadratic kind); the prefix cache is mutually
    exclusive with it for now (a seeded verifier slot has no draft-side
    snapshot).

    Durability (DESIGN.md §12): ``checkpoint_every_ticks > 0`` makes an
    engine constructed with a write-ahead ``journal=`` also write an
    atomic checkpoint every N engine ticks (at macro-step boundaries);
    ``ContinuousServingEngine.restore`` resumes from the latest valid
    one with byte-identical streams. ``debug_audit`` runs the invariant
    audit (``PagePool.check()`` + prefix-cache refcounts == live pins) at
    the end of every ``run()`` — also forced on by the
    ``REPRO_DEBUG_AUDIT`` env var (set for the test suite and chaos CI).
    """

    num_slots: int = 4
    max_len: int = 4096
    prefill_chunk: int = 128          # 0 = absorb whole prompts in one tick
    decode_ticks_per_prefill: int = 1
    max_queue: int = 0                # 0 = unbounded admission queue
    temperature: float = 0.0          # 0 = greedy
    seed: int = 0
    macro_ticks: int = 8              # K decode ticks per device dispatch
    prefill_buckets: bool = True      # pow-2 bucketing of fallback prefill
    prefill_bucket_min: int = 16      # smallest bucket
    slot_shards: int = 0              # data-axis pool shards (0 = auto)
    overload_policy: str = "reject_new"  # reject_new | shed_oldest | queue_wait
    queue_wait_ticks: int = 0         # queue_wait policy: max queue age (ticks)
    fault_guard: bool = True          # NaN/Inf lane in the decode macro-step
    fault_retries: int = 1            # re-admissions after a slot quarantine
    page_size: int = 0                # 0 = unpaged; else ring rows per page
    num_pages: int = 0                # 0 = auto (num_slots * max_len / page)
    prefix_cache_bytes: int = 0       # 0 = prefix cache off; else LRU budget
    checkpoint_every_ticks: int = 0   # 0 = no periodic engine checkpoints
    speculative: bool = False         # draft-verify decode (DESIGN.md §13)
    spec_gamma: int = 2               # draft tokens per speculative round
    debug_audit: bool = False         # invariant audit at end of run()

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.prefill_chunk < 0 or self.max_len < 1:
            raise ValueError("bad prefill_chunk/max_len")
        if self.macro_ticks < 1:
            raise ValueError("macro_ticks must be >= 1")
        if self.prefill_bucket_min < 1:
            raise ValueError("prefill_bucket_min must be >= 1")
        if self.slot_shards < 0:
            raise ValueError("slot_shards must be >= 0 (0 = auto)")
        if self.slot_shards > 1 and self.num_slots % self.slot_shards:
            raise ValueError(
                f"num_slots ({self.num_slots}) must be divisible by "
                f"slot_shards ({self.slot_shards})")
        if not math.isfinite(self.temperature) or self.temperature < 0:
            raise ValueError(
                f"temperature must be finite and >= 0 (0 = greedy), got "
                f"{self.temperature!r}")
        if self.overload_policy not in ("reject_new", "shed_oldest",
                                        "queue_wait"):
            raise ValueError(
                f"overload_policy must be one of reject_new | shed_oldest "
                f"| queue_wait, got {self.overload_policy!r}")
        if self.queue_wait_ticks < 0:
            raise ValueError("queue_wait_ticks must be >= 0 (0 = no cap)")
        if self.fault_retries < 0:
            raise ValueError("fault_retries must be >= 0")
        if self.page_size < 0 or self.num_pages < 0:
            raise ValueError("page_size/num_pages must be >= 0")
        if self.page_size and self.max_len % self.page_size:
            raise ValueError(
                f"page_size ({self.page_size}) must divide max_len "
                f"({self.max_len})")
        if self.num_pages and not self.page_size:
            raise ValueError("num_pages requires page_size > 0")
        if self.prefix_cache_bytes < 0:
            raise ValueError("prefix_cache_bytes must be >= 0")
        if self.checkpoint_every_ticks < 0:
            raise ValueError("checkpoint_every_ticks must be >= 0 (0 = off)")
        if self.spec_gamma < 1:
            raise ValueError("spec_gamma must be >= 1")
        if self.speculative and self.prefix_cache_bytes:
            raise ValueError(
                "speculative decoding and the prefix cache are mutually "
                "exclusive (a prefix-seeded verifier slot has no draft-side "
                "snapshot to seed from)")


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    mode: str                       # train | prefill | decode


SHAPE_CELLS = (
    ShapeCell("train_4k", 4_096, 256, "train"),
    ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    ShapeCell("decode_32k", 32_768, 128, "decode"),
    ShapeCell("long_500k", 524_288, 1, "decode"),
)


def get_cell(name: str) -> ShapeCell:
    for c in SHAPE_CELLS:
        if c.name == name:
            return c
    raise KeyError(name)


def input_specs(cfg: ArchConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, L = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    act = cfg.activation_dtype

    def tok(shape):
        return jax.ShapeDtypeStruct(shape, i32)

    if cell.mode == "train":
        specs = {}
        lt = L
        if cfg.frontend == "vision":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), act)
            lt = L - cfg.num_patches
        if cfg.frontend == "audio":
            specs["frame_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), act)
        specs["tokens"] = tok((B, lt))
        specs["labels"] = tok((B, lt))
        return specs
    if cell.mode == "prefill":
        specs = {}
        lt = L
        if cfg.frontend == "vision":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), act)
            lt = L - cfg.num_patches
        if cfg.frontend == "audio":
            specs["frame_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), act)
        specs["tokens"] = tok((B, lt))
        return specs
    # decode: one new token; the cache (sized for seq_len) is a separate
    # donated argument produced by serving.init_cache_specs.
    return {"tokens": tok((B, 1))}
