"""Mamba-2 SSD (state-space duality) block, chunk-parallel, TPU-friendly.

The SSD recurrence per head (state h in R^{hd x ds}):

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T ,   y_t = h_t C_t + D x_t

is computed with the same chunk-parallel decomposition as SLAY's causal
linear attention (intra-chunk quadratic + inter-chunk carried state), which
is exactly the "duality" of the SSD paper: within a chunk the recurrence is
a masked, decay-weighted attention on (C, B); across chunks the state is a
compact (nheads, headdim, dstate) carry. All contractions are MXU-shaped
matmuls; decay weights are rank-1 outer products of cumulative log-decays.

Shapes: x (B, L, nh, hd), b/c (B, L, ng, ds) broadcast over heads,
dt (B, L, nh) [post-softplus], a_log (nh,). All accumulation fp32.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec


class SsmState(NamedTuple):
    h: jnp.ndarray     # (..., nh, hd, ds) fp32
    conv: jnp.ndarray  # (..., W-1, conv_dim) rolling conv inputs


def ssd_specs(d_model: int, d_state: int, expand: int = 2,
              head_dim: int = 64, ngroups: int = 1, conv_width: int = 4):
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    conv_dim = d_inner + 2 * ngroups * d_state
    return {
        "in_proj": ParamSpec(
            (d_model, 2 * d_inner + 2 * ngroups * d_state + nheads),
            ("embed", "mlp")),
        "conv_w": ParamSpec((conv_width, conv_dim), (None, "mlp"),
                            scale=0.5),
        "conv_b": ParamSpec((conv_dim,), ("mlp",), init="zeros"),
        "a_log": ParamSpec((nheads,), (None,), init="ones"),
        "dt_bias": ParamSpec((nheads,), (None,), init="zeros"),
        "d_skip": ParamSpec((nheads,), (None,), init="ones"),
        "norm": ParamSpec((d_inner,), ("mlp",), init="zeros"),
        "out_proj": ParamSpec((d_inner, d_model), ("mlp", "embed")),
    }


def _split_proj(params, x, d_model, d_state, expand, head_dim, ngroups):
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    zxbcdt = x @ params["in_proj"]
    z, xs, b, c, dt = jnp.split(
        zxbcdt,
        [d_inner, 2 * d_inner, 2 * d_inner + ngroups * d_state,
         2 * d_inner + 2 * ngroups * d_state], axis=-1)
    return z, xs, b, c, dt, d_inner, nheads


def _causal_conv(params, u, w: int, tail=None):
    """Depthwise causal conv, width w. u (..., L, C) -> (out, new tail).

    ``tail`` (..., W-1, C) is the previous chunk's pre-conv rows (chunked
    prefill continuation); None means a fresh sequence start (zero left
    context). The new tail is the last W-1 rows of [tail, u] — what the
    next chunk's first positions need as their left context."""
    if tail is None:
        tail = jnp.zeros((*u.shape[:-2], w - 1, u.shape[-1]), u.dtype)
    hist = jnp.concatenate([tail.astype(u.dtype), u], axis=-2)
    out = sum(hist[..., i:i + u.shape[-2], :] * params["conv_w"][i]
              for i in range(w))
    return jax.nn.silu(out + params["conv_b"]), hist[..., u.shape[-2]:, :]


def ssd_forward(params: dict, x: jnp.ndarray, *, d_state: int,
                expand: int = 2, head_dim: int = 64, ngroups: int = 1,
                conv_width: int = 4, chunk_size: int = 256) -> jnp.ndarray:
    """Full-sequence SSD block. x (B, L, d_model) -> (B, L, d_model).

    Implemented as :func:`ssd_prefill_chunk` from the zero state — the
    training forward and the serving chunked-prefill continuation are the
    same code path, so chunk-by-chunk prefill is structurally exact
    (DESIGN.md §9)."""
    state = ssd_init_state((x.shape[0],), x.shape[-1], d_state, expand,
                           head_dim, ngroups, conv_width)
    y, _ = ssd_prefill_chunk(
        params, x, state, d_state=d_state, expand=expand, head_dim=head_dim,
        ngroups=ngroups, conv_width=conv_width, chunk_size=chunk_size)
    return y


def ssd_prefill_chunk(params: dict, x: jnp.ndarray, state: SsmState, *,
                      d_state: int, expand: int = 2, head_dim: int = 64,
                      ngroups: int = 1, conv_width: int = 4,
                      chunk_size: int = 256, norm_eps: float = 1e-6
                      ) -> tuple[jnp.ndarray, SsmState]:
    """Absorb an arbitrary-length prompt chunk into an ``SsmState``.

    x (B, Lc, d_model) -> (y (B, Lc, d_model), new state). Two carries
    cross the chunk boundary (DESIGN.md §9): the (nh, hd, ds) fp32 scan
    state, which seeds the chunked scan's recurrence exactly (position t
    of this chunk reads the prefix state decayed by exp(cum_t), identical
    to the whole-prompt schedule), and the (W-1, conv_dim) causal-conv
    tail — the last W-1 pre-conv projections of the prefix, so the first
    W-1 positions of this chunk see their true left context instead of
    the zero padding a fresh sequence starts from. The conv runs in the
    activation dtype over [tail, chunk] (:func:`_causal_conv`); the fp32
    tail round-trips the activation dtype exactly. Feeding a prompt
    chunk-by-chunk therefore reproduces :func:`ssd_forward` for any chunk
    schedule, ragged tails included (the scan zero-pads internally with
    dt = 0, see :func:`_ssd_chunked`).
    """
    d_model = x.shape[-1]
    z, xs, b, c, dt, d_inner, nheads = _split_proj(
        params, x, d_model, d_state, expand, head_dim, ngroups)
    u = jnp.concatenate([xs, b, c], -1)                 # (B, Lc, conv_dim)
    B, L = x.shape[0], x.shape[-2]
    xbc, tail = _causal_conv(params, u, conv_width, tail=state.conv)
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + ngroups * d_state], -1)

    xh = xs.reshape(B, L, nheads, head_dim)
    bh = b.reshape(B, L, ngroups, d_state)
    ch = c.reshape(B, L, ngroups, d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B,L,nh)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))              # (nh,)

    y, h = _ssd_chunked(xh, bh, ch, dt, a, chunk_size,
                        init_h=state.h, return_state=True)  # (B,L,nh,hd)
    y = y + params["d_skip"].astype(jnp.float32)[:, None] * xh.astype(
        jnp.float32)
    y = y.reshape(B, L, d_inner).astype(x.dtype)
    # Gated RMS norm (mamba2's norm-before-out-proj).
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), -1, keepdims=True)
    y = (yf * jax.lax.rsqrt(var + norm_eps)
         * (1.0 + params["norm"].astype(jnp.float32))).astype(x.dtype)
    return y @ params["out_proj"], SsmState(h, tail.astype(jnp.float32))


def _ssd_chunked(xh, bh, ch, dt, a, chunk: int, *, init_h=None,
                 return_state: bool = False):
    """Chunk-parallel SSD scan. Returns (B, L, nh, hd) fp32 (optionally
    plus the final (B, nh, hd, ds) carry).

    ``init_h`` seeds the inter-chunk carry (chunked prefill continuation).
    Ragged L is zero-padded to a chunk multiple inside the kernel-shaped
    scan: padded steps carry dt = 0, so their log-decay is 0 (the decay
    factor exp(0) = 1 is the identity) and their dt-weighted score/state
    contributions vanish exactly — the final carry and every real row's
    output are untouched, for any L and chunk.
    """
    B, L, nh, hd = xh.shape
    ng, ds = bh.shape[-2], bh.shape[-1]
    if L % chunk:
        pad = chunk - L % chunk
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        out = _ssd_chunked(
            jnp.pad(xh, pad4), jnp.pad(bh, pad4), jnp.pad(ch, pad4),
            jnp.pad(dt, ((0, 0), (0, pad), (0, 0))), a, chunk,
            init_h=init_h, return_state=return_state)
        if return_state:
            return out[0][:, :L], out[1]
        return out[:, :L]
    C, T = L // chunk, chunk
    g = nh // ng  # heads per group

    xc = xh.reshape(B, C, T, nh, hd).astype(jnp.float32)
    bc = bh.reshape(B, C, T, ng, ds).astype(jnp.float32)
    cc = ch.reshape(B, C, T, ng, ds).astype(jnp.float32)
    dtc = dt.reshape(B, C, T, nh)
    # Per-step log decay and intra-chunk cumulative sums.
    la_ = dtc * a  # (B,C,T,nh) negative
    cum = jnp.cumsum(la_, axis=2)  # inclusive

    xc, bc, cc, dtc, la_, cum = (jnp.moveaxis(t, 1, 0)
                                 for t in (xc, bc, cc, dtc, la_, cum))

    def step(h, inp):
        x_c, b_c, c_c, dt_c, cum_c = inp
        # (T,T) decay matrix per head: exp(cum_t - cum_u) for u <= t.
        diff = cum_c[:, :, None, :] - cum_c[:, None, :, :]   # (B,T,T,nh)
        tri = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
        decay = jnp.where(tri, jnp.exp(diff), 0.0)
        # Intra: scores[t,u] = decay * (C_t . B_u) * dt_u
        cb = jnp.einsum("btgs,bugs->btug", c_c, b_c)         # (B,T,T,ng)
        cb = jnp.repeat(cb, g, axis=-1)                      # (B,T,T,nh)
        scores = decay * cb * dt_c[:, None, :, :]
        y = jnp.einsum("btuh,buhd->bthd", scores, x_c)
        # Inter: prefix state read out at each position, decayed by exp(cum_t).
        cg = jnp.repeat(c_c, g, axis=-2)                     # (B,T,nh,ds)
        y += jnp.einsum("bths,bhds->bthd",
                        cg * jnp.exp(cum_c)[..., None], h)
        # State update: h' = exp(cum_T) h + sum_u exp(cum_T - cum_u) dt_u x B^T
        w = jnp.exp(cum_c[:, -1:, :] - cum_c) * dt_c          # (B,T,nh)
        bg = jnp.repeat(b_c, g, axis=-2)                      # (B,T,nh,ds)
        dh_ = jnp.einsum("bthd,bths->bhds", x_c * w[..., None], bg)
        h = jnp.exp(cum_c[:, -1, :])[..., None, None] * h + dh_
        return h, y

    h0 = (jnp.zeros((B, nh, hd, ds), jnp.float32) if init_h is None
          else init_h.astype(jnp.float32))
    h_fin, ys = jax.lax.scan(step, h0, (xc, bc, cc, dtc, cum))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, L, nh, hd)
    if return_state:
        return y, h_fin
    return y


def ssd_init_state(lead_shape, d_model: int, d_state: int, expand: int = 2,
                   head_dim: int = 64, ngroups: int = 1,
                   conv_width: int = 4) -> SsmState:
    d_inner = expand * d_model
    nheads = d_inner // head_dim
    conv_dim = d_inner + 2 * ngroups * d_state
    return SsmState(
        h=jnp.zeros((*lead_shape, nheads, head_dim, d_state), jnp.float32),
        conv=jnp.zeros((*lead_shape, conv_width - 1, conv_dim), jnp.float32))


def ssd_decode_step(params: dict, x: jnp.ndarray, state: SsmState, *,
                    d_state: int, expand: int = 2, head_dim: int = 64,
                    ngroups: int = 1, conv_width: int = 4,
                    norm_eps: float = 1e-6):
    """One token. x (B, d_model) -> (B, d_model), O(nh*hd*ds) state update."""
    d_model = x.shape[-1]
    z, xs, b, c, dt, d_inner, nheads = _split_proj(
        params, x, d_model, d_state, expand, head_dim, ngroups)
    u = jnp.concatenate([xs, b, c], -1)                       # (B, conv_dim)
    hist = jnp.concatenate([state.conv, u[..., None, :].astype(jnp.float32)],
                           axis=-2)                           # (B, W, C)
    conv_out = jnp.einsum("bwc,wc->bc", hist,
                          params["conv_w"].astype(jnp.float32))
    xbc = jax.nn.silu(conv_out + params["conv_b"]).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + ngroups * d_state], -1)
    B = x.shape[0]
    xh = xs.reshape(B, nheads, head_dim).astype(jnp.float32)
    bh = jnp.repeat(b.reshape(B, ngroups, d_state), nheads // ngroups,
                    axis=-2).astype(jnp.float32)
    chd = jnp.repeat(c.reshape(B, ngroups, d_state), nheads // ngroups,
                     axis=-2).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B, nh)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a)                                    # (B, nh)
    h = (decay[..., None, None] * state.h
         + jnp.einsum("bh,bhd,bhs->bhds", dt, xh, bh))
    y = jnp.einsum("bhds,bhs->bhd", h, chd)
    y = y + params["d_skip"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(B, d_inner).astype(x.dtype) * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), -1, keepdims=True)
    y = (yf * jax.lax.rsqrt(var + norm_eps)
         * (1.0 + params["norm"].astype(jnp.float32))).astype(x.dtype)
    return y @ params["out_proj"], SsmState(h, hist[..., 1:, :])
