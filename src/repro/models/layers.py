"""Core NN layers: norms, RoPE, MLPs, chunked (flash-style) attention, MLA,
and sequence-parallel decode attention with log-sum-exp merging.

Conventions
-----------
* params are plain dicts of jnp arrays; compute dtype is bf16, softmax/norms fp32.
* TP ("model" axis) shards attention heads in train/prefill.  Query heads are
  padded up to a multiple of the TP degree at *weight layout* time (pad head
  rows of wo are zero, so outputs are exact).
* Decode shards the KV cache over the *sequence* dimension across the model
  axis (flash-decoding style): each shard attends over its local KV chunk and
  partial results merge with a log-sum-exp psum.  This supports GQA configs
  whose kv-head count does not divide the TP degree and 500k-token caches.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


# ---------------------------------------------------------------------------
# mesh context
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MeshCtx:
    mesh: Mesh
    dp: tuple            # data-parallel axis names, e.g. ("pod", "data")
    tp: str = "model"

    @property
    def dp_size(self) -> int:
        return int(math.prod(self.mesh.shape[a] for a in self.dp))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp])

    @property
    def all_axes(self) -> tuple:
        return tuple(self.dp) + (self.tp,)

    def shard(self, x, *spec):
        """Apply a sharding constraint (pjit-style)."""
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def batch_spec(self, *rest):
        return P(self.dp, *rest)

    def bspec(self, n: int):
        """DP spec entry for a batch-like dim of size n (None if indivisible,
        e.g. global_batch=1 long-context decode)."""
        return self.dp if (n % self.dp_size == 0) else None


def pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p, x, cfg, eps=1e-5):
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D) or (..., H, D) w/ scalar positions; rotates pairs."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                      # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., S, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast over head dim: x is (..., S, H, D); ang (..., S, d/2)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# explicit-TP einsum wrappers (perf opt, cfg.explicit_tp)
#
# GSPMD keeps the f32 dot accumulator live across the tensor-parallel
# all-reduce when the consumer chain upcasts (norms/softmax), doubling
# activation-AR bytes.  These shard_map wrappers pin the collective to the
# declared bf16 value: column-parallel (x replicated over TP -> backward
# psums dx in bf16), row-parallel (explicit bf16 psum of partial outputs).
# ---------------------------------------------------------------------------
def tp_col_einsum(spec_eq, x, w, mcx: MeshCtx, *, w_spec, out_spec,
                  x_spec=None):
    """Column-parallel: w sharded on an output dim; x replicated over TP."""
    if mcx is None or mcx.tp_size == 1:
        return jnp.einsum(spec_eq, x, w)
    bs = mcx.bspec(x.shape[0])
    xs = x_spec if x_spec is not None else P(bs, *([None] * (x.ndim - 1)))

    def inner(x_l, w_l):
        return jnp.einsum(spec_eq, x_l, w_l)

    return shard_map(inner, mesh=mcx.mesh, in_specs=(xs, w_spec),
                         out_specs=out_spec)(x, w)


def tp_row_einsum(spec_eq, x, w, mcx: MeshCtx, *, x_spec, w_spec, out_spec):
    """Row-parallel: contraction dim sharded; explicit bf16 psum."""
    if mcx is None or mcx.tp_size == 1:
        return jnp.einsum(spec_eq, x, w)

    def inner(x_l, w_l):
        y = jnp.einsum(spec_eq, x_l, w_l)
        return jax.lax.psum(y, mcx.tp)

    return shard_map(inner, mesh=mcx.mesh, in_specs=(x_spec, w_spec),
                         out_specs=out_spec)(x, w)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_mlp(cfg, rng, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    s = 0.02
    dt = jnp.dtype(cfg.dtype)
    p = {}
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = (jax.random.normal(k1, (d, ff)) * s).astype(dt)
        p["w_up"] = (jax.random.normal(k2, (d, ff)) * s).astype(dt)
        p["w_down"] = (jax.random.normal(k3, (ff, d)) * s).astype(dt)
    else:
        p["w_up"] = (jax.random.normal(k1, (d, ff)) * s).astype(dt)
        p["w_down"] = (jax.random.normal(k2, (ff, d)) * s).astype(dt)
        if cfg.use_bias:
            p["b_up"] = jnp.zeros((ff,), dt)
            p["b_down"] = jnp.zeros((d,), dt)
    return p


def apply_mlp(p, x, cfg, mcx: Optional[MeshCtx] = None):
    if cfg.explicit_tp and mcx is not None and mcx.tp_size > 1 \
            and p["w_down"].shape[0] % mcx.tp_size == 0 and x.ndim == 3:
        return _apply_mlp_explicit_tp(p, x, cfg, mcx)
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        u = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = jnp.einsum("...d,df->...f", x, p["w_up"])
        if "b_up" in p:
            h = h + p["b_up"]
        if cfg.mlp_type == "squared_relu":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("...f,fd->...d", h, p["w_down"])
    if "b_down" in p:
        y = y + p["b_down"]
    return y


def _apply_mlp_explicit_tp(p, x, cfg, mcx: MeshCtx):
    """Whole MLP in one shard_map: column-parallel up projections, local
    activation (bias slice added locally), row-parallel down projection with
    explicit bf16 psum."""
    bs = mcx.bspec(x.shape[0])
    xs = P(bs, None, None)

    if cfg.mlp_type == "swiglu":
        ws = [p["w_gate"], p["w_up"], p["w_down"]]
        w_specs = [P(None, mcx.tp), P(None, mcx.tp), P(mcx.tp, None)]
    else:
        ws = [p["w_up"], p["w_down"]]
        w_specs = [P(None, mcx.tp), P(mcx.tp, None)]
    has_bias = "b_up" in p
    if has_bias:
        ws.append(p["b_up"])
        w_specs.append(P(mcx.tp))

    def inner(x_l, *ws_l):
        if cfg.mlp_type == "swiglu":
            wg, wu, wd = ws_l[0], ws_l[1], ws_l[2]
            g = jnp.einsum("bsd,df->bsf", x_l, wg)
            u = jnp.einsum("bsd,df->bsf", x_l, wu)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(x_l.dtype) * u
        else:
            wu, wd = ws_l[0], ws_l[1]
            h = jnp.einsum("bsd,df->bsf", x_l, wu)
            if has_bias:
                h = h + ws_l[-1]
            if cfg.mlp_type == "squared_relu":
                h = jnp.square(jax.nn.relu(h))
            else:
                h = jax.nn.gelu(h.astype(jnp.float32)).astype(x_l.dtype)
        y = jnp.einsum("bsf,fd->bsd", h, wd)
        return jax.lax.psum(y, mcx.tp)

    y = shard_map(inner, mesh=mcx.mesh,
                      in_specs=tuple([xs] + w_specs),
                      out_specs=xs)(x, *ws)
    if "b_down" in p:
        y = y + p["b_down"]
    return y


# ---------------------------------------------------------------------------
# GQA attention (train / prefill): chunked online-softmax, never S x S
# ---------------------------------------------------------------------------
def init_attention(cfg, rng, mcx: Optional[MeshCtx] = None):
    tp = mcx.tp_size if mcx is not None else 1
    H = pad_to(cfg.num_heads, tp)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 4)
    s = 0.02

    def z_pad(w, n_real, n_pad, axis):
        """zero out padded head slots"""
        if n_real == n_pad:
            return w
        idx = [slice(None)] * w.ndim
        idx[axis] = slice(n_real, n_pad)
        return w.at[tuple(idx)].set(0.0)

    p = {
        "wq": z_pad((jax.random.normal(ks[0], (d, H, hd)) * s), cfg.num_heads, H, 1).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, KV, hd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, KV, hd)) * s).astype(dt),
        "wo": z_pad((jax.random.normal(ks[3], (H, hd, d)) * s), cfg.num_heads, H, 0).astype(dt),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KV, hd), dt)
        p["bv"] = jnp.zeros((KV, hd), dt)
        p["bo"] = jnp.zeros((d,), dt)
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _qk_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def flash_attention(q, k, v, *, causal: bool, chunk: int, mcx: Optional[MeshCtx]):
    """Chunked attention.  q: (B,S,H,D); k,v: (B,S,H,D) (kv already repeated to
    padded H).  Scans q-chunks (outer) and kv-chunks (inner, online softmax).
    Never materializes (S, S)."""
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    c = min(chunk, S)
    S_real = S
    if S % c:
        pad = c - S % c
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nq = S // c
    scale = 1.0 / math.sqrt(D)
    qc = q.reshape(B, nq, c, H, D)
    kc = k.reshape(B, nq, c, H, D)
    vc = v.reshape(B, nq, c, H, Dv)

    def q_block(qi):
        qb, q_idx = qi                                     # (B,c,H,D), ()
        q_pos = q_idx * c + jnp.arange(c)

        def kv_step(carry, kvi):
            m, l, acc = carry
            kb, vb, k_idx = kvi
            k_pos = k_idx * c + jnp.arange(c)
            s_blk = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            mask = jnp.broadcast_to(k_pos[None, :] < S_real, (c, c))
            if causal:
                mask = jnp.logical_and(mask, q_pos[:, None] >= k_pos[None, :])
            s_blk = jnp.where(mask[None, None], s_blk, -1e30)
            m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
            p_blk = jnp.exp(s_blk - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p_blk, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_blk.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, c), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, c), jnp.float32)
        a0 = jnp.zeros((B, H, c, Dv), jnp.float32)
        ks = jnp.moveaxis(kc, 1, 0)                        # (nq,B,c,H,D)
        vs = jnp.moveaxis(vc, 1, 0)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (ks, vs, jnp.arange(nq)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2)                     # (B,c,H,D)

    qs = jnp.moveaxis(qc, 1, 0)                            # (nq,B,c,H,D)
    outs = jax.lax.map(q_block, (qs, jnp.arange(nq)))      # (nq,B,c,H,Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, Dv)
    return out[:, :S_real].astype(q.dtype)


# ---------------------------------------------------------------------------
# custom-VJP flash attention: the scan formulation above is memory-correct in
# the forward pass but plain autodiff saves every probs block as a scan
# residual (S x S traffic + memory in the backward).  This version saves only
# (q, k, v, out, m, l) and recomputes probs blockwise in the backward — the
# standard flash-attention backward, expressed in XLA.
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, causal: bool, chunk: int):
    out, _, _ = _flash_fwd_impl(q, k, v, causal, chunk)
    return out


def _flash_fwd_impl(q, k, v, causal, c):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    nq = S // c
    scale = 1.0 / math.sqrt(D)
    qc = jnp.moveaxis(q.reshape(B, nq, c, H, D), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nq, c, H, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nq, c, H, Dv), 1, 0)

    def q_block(qi):
        qb, q_idx = qi
        q_pos = q_idx * c + jnp.arange(c)

        def kv_step(carry, kvi):
            m, l, acc = carry
            kb, vb, k_idx = kvi
            k_pos = k_idx * c + jnp.arange(c)
            s_blk = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s_blk = jnp.where(mask[None, None], s_blk, -1e30)
            m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
            p_blk = jnp.exp(s_blk - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p_blk, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_blk.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, c), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, c), jnp.float32)
        a0 = jnp.zeros((B, H, c, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kc, vc, jnp.arange(nq)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(o, 1, 2), m, l      # (B,c,H,Dv), (B,H,c)

    outs, ms, ls = jax.lax.map(q_block, (qc, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, Dv).astype(q.dtype)
    m = jnp.moveaxis(ms, 0, 2).reshape(B, H, S)             # (B,H,S)
    l = jnp.moveaxis(ls, 0, 2).reshape(B, H, S)
    return out, m, l


def _flash_fwd(q, k, v, causal, chunk):
    out, m, l = _flash_fwd_impl(q, k, v, causal, chunk)
    return out, (q, k, v, out, m, l)


def _flash_bwd(causal, c, res, g):
    q, k, v, out, m, l = res
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    nq = S // c
    scale = 1.0 / math.sqrt(D)
    # D_i = rowsum(dO * O)  (B,H,S)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # (B,S,H)
    delta = jnp.moveaxis(delta, 1, 2)                         # (B,H,S)
    qc = jnp.moveaxis(q.reshape(B, nq, c, H, D), 1, 0)
    kc = jnp.moveaxis(k.reshape(B, nq, c, H, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nq, c, H, Dv), 1, 0)
    gc = jnp.moveaxis(g.reshape(B, nq, c, H, Dv), 1, 0)

    def q_block(carry, xs):
        dk, dv = carry                                       # (nq,B,c,H,D) f32
        qb, gb, q_idx = xs
        q_pos = q_idx * c + jnp.arange(c)
        m_i = jax.lax.dynamic_slice_in_dim(m, q_idx * c, c, axis=2)
        l_i = jax.lax.dynamic_slice_in_dim(l, q_idx * c, c, axis=2)
        d_i = jax.lax.dynamic_slice_in_dim(delta, q_idx * c, c, axis=2)

        def kv_step(dq_acc, kvj):
            kb, vb, dk_j, dv_j, k_idx = kvj
            k_pos = k_idx * c + jnp.arange(c)
            s_blk = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s_blk = jnp.where(mask[None, None], s_blk, -1e30)
            p = jnp.exp(s_blk - m_i[..., None]) / \
                jnp.maximum(l_i, 1e-30)[..., None]            # (B,H,c,c)
            dv_j = dv_j + jnp.einsum("bhqk,bqhd->bkhd", p,
                                     gb.astype(jnp.float32))
            dp = jnp.einsum("bqhd,bkhd->bhqk", gb.astype(jnp.float32),
                            vb.astype(jnp.float32))
            ds = p * (dp - d_i[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                         kb.astype(jnp.float32))
            dk_j = dk_j + jnp.einsum("bhqk,bqhd->bkhd", ds,
                                     qb.astype(jnp.float32))
            return dq_acc, (dk_j, dv_j)

        dq0 = jnp.zeros((B, c, H, D), jnp.float32)
        dq_i, (dk, dv) = jax.lax.scan(
            kv_step, dq0, (kc, vc, dk, dv, jnp.arange(nq)))
        return (dk, dv), dq_i

    dk0 = jnp.zeros((nq, B, c, H, D), jnp.float32)
    dv0 = jnp.zeros((nq, B, c, H, Dv), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(q_block, (dk0, dv0),
                                 (qc, gc, jnp.arange(nq)))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, S, H, D).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, S, H, D).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, S, H, Dv).astype(v.dtype)
    return dq, dk, dv


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_vjp(q, k, v, *, causal: bool, chunk: int,
                        mcx: Optional[MeshCtx]):
    """Padded wrapper around the custom-vjp flash core."""
    B, S, H, D = q.shape
    c = min(chunk, S)
    S_real = S
    if S % c:
        pad = c - S % c
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad keys at a *masked-out* position: give them q_pos > everything
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if not causal and S % c:
        # non-causal needs explicit masking of padded keys; fall back
        return flash_attention(q[:, :S_real + (c - S_real % c) % c],
                               k, v, causal=causal, chunk=chunk,
                               mcx=mcx)[:, :S_real]
    out = _flash_core(q, k, v, causal, c)
    return out[:, :S_real]


def causal_tree_attention(q, k, v, *, chunk: int, mcx: Optional[MeshCtx]):
    """Binary-tree causal packing (perf optimization, see EXPERIMENTS §Perf).

    causal(S) = causal on each half + *unmasked* dense cross-attention of the
    second half onto the first half.  Recursing log2(S/chunk) times evaluates
    the causal triangle with dense rectangles only — removing the ~2x masked-
    FLOP waste of the scan formulation.  Combination uses log-sum-exp merge.
    """
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)

    def dense_block(qb, kb, vb, causal_mask):
        # qb: (..., sq, H, D) small enough to do directly per recursion leaf
        s_blk = jnp.einsum("...qhd,...khd->...hqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
        if causal_mask:
            sq, sk = s_blk.shape[-2], s_blk.shape[-1]
            mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
            s_blk = jnp.where(mask, s_blk, -1e30)
        m = jnp.max(s_blk, axis=-1)
        p = jnp.exp(s_blk - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("...hqk,...khd->...hqd", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        return m, l, o

    def merge(a, b):
        (ma, la, oa), (mb, lb, ob) = a, b
        m = jnp.maximum(ma, mb)
        ca, cb = jnp.exp(ma - m), jnp.exp(mb - m)
        return m, la * ca + lb * cb, oa * ca[..., None] + ob * cb[..., None]

    def rec(qb, kb, vb):
        s = qb.shape[-3]
        if s <= chunk:
            return dense_block(qb, kb, vb, True)
        h = s // 2
        q1, q2 = qb[..., :h, :, :], qb[..., h:, :, :]
        k1, k2 = kb[..., :h, :, :], kb[..., h:, :, :]
        v1, v2 = vb[..., :h, :, :], vb[..., h:, :, :]
        m1, l1, o1 = rec(q1, k1, v1)
        m2a, l2a, o2a = rec(q2, k2, v2)
        m2b, l2b, o2b = dense_block(q2, k1, v1, False)     # dense rectangle
        m2, l2, o2 = merge((m2a, l2a, o2a), (m2b, l2b, o2b))
        return (jnp.concatenate([m1, m2], axis=-1),
                jnp.concatenate([l1, l2], axis=-1),
                jnp.concatenate([o1, o2], axis=-2))

    m, l, o = rec(q, k, v)                                 # o: (B,H,S,D)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)         # (B,S,H,D)


def repeat_kv(x, h_out: int):
    """(B,S,KV,D) -> (B,S,h_out,D) by group repetition."""
    B, S, KV, D = x.shape
    rep = h_out // KV
    return jnp.broadcast_to(x[:, :, :, None, :], (B, S, KV, rep, D)).reshape(
        B, S, h_out, D)


def attention_fwd(p, x, cfg, mcx: MeshCtx, *, positions, causal=True,
                  return_kv=False):
    """Train/prefill attention.  x: (B,S,d)."""
    B, S, d = x.shape
    tp = mcx.tp_size
    H = pad_to(cfg.num_heads, tp)
    KV, hd = cfg.num_kv_heads, cfg.head_dim

    bs = mcx.bspec(B) if mcx is not None else None
    use_xtp = (cfg.explicit_tp and mcx is not None and mcx.tp_size > 1)
    if use_xtp:
        q = tp_col_einsum("bsd,dhk->bshk", x, p["wq"], mcx,
                          w_spec=P(None, mcx.tp, None),
                          out_spec=P(bs, None, mcx.tp, None))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = _qk_norm(q, p["q_norm"])
        k = _qk_norm(k, p["k_norm"])
    if cfg.attn_type != "nope" and cfg.rope_theta and not cfg.is_encoder:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = mcx.shard(q, mcx.dp, None, mcx.tp, None)
    kv_cache = (k, v) if return_kv else None
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    k = mcx.shard(k, mcx.dp, None, mcx.tp, None)
    v = mcx.shard(v, mcx.dp, None, mcx.tp, None)
    if causal and cfg.causal_tree_attn:
        out = causal_tree_attention(q, k, v, chunk=cfg.attn_chunk, mcx=mcx)
    elif cfg.flash_vjp:
        out = flash_attention_vjp(q, k, v, causal=causal,
                                  chunk=cfg.attn_chunk, mcx=mcx)
    else:
        out = flash_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk, mcx=mcx)
    # pin the output (and thus the bwd cotangent) to head-sharding so token
    # shardings from neighbouring blocks (e.g. a2a MoE) never propagate into
    # the attention backward
    out = mcx.shard(out, mcx.bspec(B), None, mcx.tp, None)
    if use_xtp:
        y = tp_row_einsum("bshk,hkd->bsd", out, p["wo"], mcx,
                          x_spec=P(bs, None, mcx.tp, None),
                          w_spec=P(mcx.tp, None, None),
                          out_spec=P(bs, None, None))
    else:
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    if return_kv:
        return y, kv_cache
    return y


# ---------------------------------------------------------------------------
# GQA decode attention: sequence-sharded KV cache + LSE merge over TP
# ---------------------------------------------------------------------------
def gqa_decode_attention(p, x, cache, pos, cfg, mcx: MeshCtx):
    """One-token decode.  x: (B,1,d).  cache: dict(k,v): (B,S,KV,hd), sharded
    (dp, tp, None, None) — sequence dim split over the model axis.

    Returns (y (B,1,d), new_cache).
    """
    B = x.shape[0]
    tp = mcx.tp_size
    H = pad_to(cfg.num_heads, tp)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    S = cache["k"].shape[1]
    G = H // KV

    q = jnp.einsum("bd,dhk->bhk", x[:, 0], p["wq"])
    k_new = jnp.einsum("bd,dhk->bhk", x[:, 0], p["wk"])
    v_new = jnp.einsum("bd,dhk->bhk", x[:, 0], p["wv"])
    if "bq" in p:
        q, k_new, v_new = q + p["bq"], k_new + p["bk"], v_new + p["bv"]
    if "q_norm" in p:
        q = _qk_norm(q, p["q_norm"])
        k_new = _qk_norm(k_new, p["k_norm"])
    if cfg.rope_theta and not cfg.is_encoder:
        q = apply_rope(q[:, None], jnp.full((B, 1), pos), cfg.rope_theta)[:, 0]
        k_new = apply_rope(k_new[:, None], jnp.full((B, 1), pos),
                           cfg.rope_theta)[:, 0]

    def inner(q_l, k_new_l, v_new_l, ck, cv):
        # local shapes: q (Bl,H,hd), cache (Bl, S_loc, KV, hd)
        S_loc = ck.shape[1]
        shard = jax.lax.axis_index(mcx.tp)
        local_idx = pos - shard * S_loc
        ok = jnp.logical_and(local_idx >= 0, local_idx < S_loc)
        li = jnp.clip(local_idx, 0, S_loc - 1)
        ck_up = jax.lax.dynamic_update_slice(
            ck, k_new_l[:, None], (0, li, 0, 0))
        cv_up = jax.lax.dynamic_update_slice(
            cv, v_new_l[:, None], (0, li, 0, 0))
        ck = jnp.where(ok, ck_up, ck)
        cv = jnp.where(ok, cv_up, cv)
        # grouped attention over local chunk
        qg = q_l.reshape(q_l.shape[0], KV, G, hd)
        s_loc = jnp.einsum("bkgd,bskd->bkgs", qg, ck,
                           preferred_element_type=jnp.float32)
        s_loc = s_loc / math.sqrt(hd)
        k_pos = shard * S_loc + jnp.arange(S_loc)
        valid = k_pos <= pos
        s_loc = jnp.where(valid[None, None, None, :], s_loc, -1e30)
        m_loc = jnp.max(s_loc, axis=-1)
        p_loc = jnp.exp(s_loc - m_loc[..., None])
        l_loc = jnp.sum(p_loc, axis=-1)
        o_loc = jnp.einsum("bkgs,bskd->bkgd", p_loc.astype(cv.dtype), cv,
                           preferred_element_type=jnp.float32)
        # log-sum-exp merge across the model axis
        m_g = jax.lax.pmax(m_loc, mcx.tp)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, mcx.tp)
        o_g = jax.lax.psum(o_loc * corr[..., None], mcx.tp)
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(q_l.shape[0], KV * G, hd), ck, cv

    bs = mcx.bspec(B)
    out, ck, cv = shard_map(
        inner,
        mesh=mcx.mesh,
        in_specs=(P(bs, None, None), P(bs, None, None),
                  P(bs, None, None),
                  P(bs, mcx.tp, None, None), P(bs, mcx.tp, None, None)),
        out_specs=(P(bs, None, None),
                   P(bs, mcx.tp, None, None), P(bs, mcx.tp, None, None)),
    )(q, k_new, v_new, cache["k"], cache["v"])

    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return y[:, None], {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (deepseek-v3)
# ---------------------------------------------------------------------------
def init_mla(cfg, rng, mcx: Optional[MeshCtx] = None):
    d = cfg.d_model
    H = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 8)
    s = 0.02
    return {
        "wq_a": (jax.random.normal(ks[0], (d, qr)) * s).astype(dt),
        "q_a_norm": jnp.ones((qr,), jnp.float32),
        "wq_b": (jax.random.normal(ks[1], (qr, H, dn + dr)) * s).astype(dt),
        "wkv_a": (jax.random.normal(ks[2], (d, kvr + dr)) * s).astype(dt),
        "kv_a_norm": jnp.ones((kvr,), jnp.float32),
        "wk_b": (jax.random.normal(ks[3], (kvr, H, dn)) * s).astype(dt),
        "wv_b": (jax.random.normal(ks[4], (kvr, H, dv)) * s).astype(dt),
        "wo": (jax.random.normal(ks[5], (H, dv, d)) * s).astype(dt),
    }


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def mla_fwd(p, x, cfg, mcx: MeshCtx, *, positions, return_kv=False):
    """MLA train/prefill: non-absorbed (matmul-friendly) path."""
    B, S, d = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank

    bs = mcx.bspec(B) if mcx is not None else None
    use_xtp = (cfg.explicit_tp and mcx is not None and mcx.tp_size > 1
               and H % mcx.tp_size == 0)

    def col(eq, xx, w):
        if use_xtp:
            return tp_col_einsum(eq, xx, w, mcx,
                                 w_spec=P(None, mcx.tp, None),
                                 out_spec=P(bs, None, mcx.tp, None))
        return jnp.einsum(eq, xx, w)

    q_lat = _rms(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_a_norm"])
    q = col("bsr,rhk->bshk", q_lat, p["wq_b"])             # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])        # (B,S,kvr+dr)
    c_kv = _rms(kv_a[..., :kvr], p["kv_a_norm"])
    k_rope = apply_rope(kv_a[..., None, kvr:], positions, cfg.rope_theta)

    k_nope = col("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = col("bsr,rhk->bshk", c_kv, p["wv_b"])
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
    q_full = mcx.shard(q_full, mcx.dp, None, mcx.tp, None)
    k_full = mcx.shard(k_full, mcx.dp, None, mcx.tp, None)
    v = mcx.shard(v, mcx.dp, None, mcx.tp, None)
    if cfg.flash_vjp:
        out = flash_attention_vjp(q_full, k_full, v, causal=True,
                                  chunk=cfg.attn_chunk, mcx=mcx)
    else:
        out = flash_attention(q_full, k_full, v, causal=True,
                              chunk=cfg.attn_chunk, mcx=mcx)
    if use_xtp:
        y = tp_row_einsum("bshk,hkd->bsd", out, p["wo"], mcx,
                          x_spec=P(bs, None, mcx.tp, None),
                          w_spec=P(mcx.tp, None, None),
                          out_spec=P(bs, None, None))
    else:
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if return_kv:
        return y, (c_kv, k_rope[:, :, 0, :])
    return y


def mla_decode_attention(p, x, cache, pos, cfg, mcx: MeshCtx):
    """Absorbed MLA decode: scores/context computed in the 512-d latent space.
    cache: {"c_kv": (B,S,kvr), "k_rope": (B,S,dr)}, seq-sharded over TP."""
    B = x.shape[0]
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank

    q_lat = _rms(jnp.einsum("bd,dr->br", x[:, 0], p["wq_a"]), p["q_a_norm"])
    q = jnp.einsum("br,rhk->bhk", q_lat, p["wq_b"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope[:, None], jnp.full((B, 1), pos),
                        cfg.rope_theta)[:, 0]
    # absorb: q_nope (B,H,dn) @ wk_b (kvr,H,dn) -> (B,H,kvr)
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope, p["wk_b"])

    kv_a = jnp.einsum("bd,dr->br", x[:, 0], p["wkv_a"])
    c_new = _rms(kv_a[..., :kvr], p["kv_a_norm"])
    kr_new = apply_rope(kv_a[:, None, None, kvr:], jnp.full((B, 1), pos),
                        cfg.rope_theta)[:, 0, 0]

    def inner(q_abs_l, q_rope_l, c_new_l, kr_new_l, cc, ckr):
        S_loc = cc.shape[1]
        shard = jax.lax.axis_index(mcx.tp)
        local_idx = pos - shard * S_loc
        ok = jnp.logical_and(local_idx >= 0, local_idx < S_loc)
        li = jnp.clip(local_idx, 0, S_loc - 1)
        cc = jnp.where(ok, jax.lax.dynamic_update_slice(
            cc, c_new_l[:, None], (0, li, 0)), cc)
        ckr = jnp.where(ok, jax.lax.dynamic_update_slice(
            ckr, kr_new_l[:, None], (0, li, 0)), ckr)
        s_loc = (jnp.einsum("bhr,bsr->bhs", q_abs_l, cc,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bhk,bsk->bhs", q_rope_l, ckr,
                              preferred_element_type=jnp.float32))
        s_loc = s_loc / math.sqrt(dn + dr)
        k_pos = shard * S_loc + jnp.arange(S_loc)
        s_loc = jnp.where((k_pos <= pos)[None, None, :], s_loc, -1e30)
        m_loc = jnp.max(s_loc, axis=-1)
        p_loc = jnp.exp(s_loc - m_loc[..., None])
        l_loc = jnp.sum(p_loc, axis=-1)
        ctx_loc = jnp.einsum("bhs,bsr->bhr", p_loc.astype(cc.dtype), cc,
                             preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_loc, mcx.tp)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, mcx.tp)
        ctx_g = jax.lax.psum(ctx_loc * corr[..., None], mcx.tp)
        ctx = (ctx_g / jnp.maximum(l_g, 1e-30)[..., None])
        return ctx.astype(q_abs_l.dtype), cc, ckr

    bs = mcx.bspec(B)
    ctx, cc, ckr = shard_map(
        inner,
        mesh=mcx.mesh,
        in_specs=(P(bs, None, None), P(bs, None, None),
                  P(bs, None), P(bs, None),
                  P(bs, mcx.tp, None), P(bs, mcx.tp, None)),
        out_specs=(P(bs, None, None),
                   P(bs, mcx.tp, None), P(bs, mcx.tp, None)),
    )(q_abs, q_rope, c_new, kr_new, cache["c_kv"], cache["k_rope"])

    # un-absorb: ctx (B,H,kvr) @ wv_b (kvr,H,dv) -> (B,H,dv)
    out = jnp.einsum("bhr,rhk->bhk", ctx, p["wv_b"])
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])
    return y[:, None], {"c_kv": cc, "k_rope": ckr}
