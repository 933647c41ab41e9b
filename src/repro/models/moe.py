"""Mixture-of-Experts layer: top-k routing, expert parallelism over the model
axis.

Baseline dispatch ("local+psum"): every TP shard holds E/tp experts; tokens
are replicated across TP.  Each shard scatters the assignments routed to its
*local* experts into a capacity-bounded (E_loc, C, d) buffer, applies the
expert FFNs as one grouped matmul, scatter-adds results back to token slots
and the shards psum-combine.  One code path serves train / prefill / decode.

Alternative dispatch ("a2a", used by the §Perf hillclimb): tokens are
sequence-sharded across TP as well; buffers exchange via all_to_all so each
token copy moves point-to-point instead of being all-reduced.  Selected with
``moe_dispatch='a2a'``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import MeshCtx
from jax import shard_map


def init_moe(cfg, rng):
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 6)
    s = 0.02
    p = {
        "router": (jax.random.normal(ks[0], (d, E)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, ff)) * s).astype(dt),
        "w_up": (jax.random.normal(ks[2], (E, d, ff)) * s).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, ff, d)) * s).astype(dt),
    }
    if cfg.num_shared_experts:
        sf = cfg.num_shared_experts * ff
        p["ws_gate"] = (jax.random.normal(ks[4], (d, sf)) * s).astype(dt)
        p["ws_up"] = (jax.random.normal(ks[5], (d, sf)) * s).astype(dt)
        p["ws_down"] = (jax.random.normal(ks[0], (sf, d)) * s).astype(dt)
    return p


def _expert_ffn(wg, wu, wd, x):
    """x: (E_loc, C, d) grouped matmul."""
    g = jnp.einsum("ecd,edf->ecf", x, wg)
    u = jnp.einsum("ecd,edf->ecf", x, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_fwd(p, x, cfg, mcx: Optional[MeshCtx]):
    """x: (B,S,d) -> (B,S,d) (+aux loss stored via jax 'aux' return).

    Returns (y, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    tp = mcx.tp_size if mcx is not None else 1
    assert E % tp == 0
    E_loc = E // tp
    xt = x.reshape(B * S, d)
    T = B * S

    if mcx is not None and cfg.moe_dispatch == "a2a" \
            and T % (mcx.dp_size * mcx.tp_size) == 0:
        y, aux = _moe_a2a(p, xt, cfg, mcx)
        y = y.reshape(B, S, d)
        # contain the (dp x tp) token sharding to this block: back to the
        # residual stream's (dp, -, -) layout so sharding propagation never
        # pushes 256-way token sharding into the attention bwd
        y = mcx.shard(y, mcx.bspec(B), None, None)
        if "ws_gate" in p:
            g = jnp.einsum("bsd,df->bsf", x, p["ws_gate"])
            u = jnp.einsum("bsd,df->bsf", x, p["ws_up"])
            h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
            y = y + jnp.einsum("bsf,fd->bsd", h, p["ws_down"])
        return y, aux

    # ---- routing (computed replicated over TP; fp32) ----
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                  # (T,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
    router_mean = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * router_mean) * E * cfg.router_aux_coef

    C = max(1, int(math.ceil(T * k * cfg.capacity_factor / E)))

    def shard_body(xt_l, top_p_l, top_e_l, wg, wu, wd):
        """Per-device: xt (T_dp, d) [replicated over tp], experts local slice."""
        tp_idx = jax.lax.axis_index(mcx.tp) if mcx is not None else 0
        e_lo = tp_idx * E_loc
        T_l = xt_l.shape[0]
        flat_e = top_e_l.reshape(-1)                         # (T_l*k,)
        flat_p = top_p_l.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_l), k)
        local = jnp.logical_and(flat_e >= e_lo, flat_e < e_lo + E_loc)
        le = jnp.where(local, flat_e - e_lo, E_loc)          # E_loc = trash row
        # position within expert: stable rank among same-expert assignments
        order = jnp.argsort(le, stable=True)
        le_s = le[order]
        pos_s = jnp.arange(T_l * k) - jnp.searchsorted(le_s, le_s, side="left")
        pos = jnp.zeros_like(pos_s).at[order].set(pos_s)
        ok = jnp.logical_and(local, pos < C)
        slot = jnp.where(ok, le * C + pos, E_loc * C)        # overflow -> trash
        buf = jnp.zeros((E_loc * C + 1, d), xt_l.dtype)
        buf = buf.at[slot].set(jnp.where(ok[:, None], xt_l[flat_t], 0.0))
        out = _expert_ffn(wg, wu, wd, buf[:E_loc * C].reshape(E_loc, C, d))
        out = out.reshape(E_loc * C, d)
        contrib = jnp.where(ok[:, None], out[jnp.clip(slot, 0, E_loc * C - 1)], 0.0)
        y_l = jnp.zeros((T_l, d), xt_l.dtype)
        y_l = y_l.at[flat_t].add(contrib * flat_p[:, None].astype(xt_l.dtype))
        if mcx is not None:
            y_l = jax.lax.psum(y_l, mcx.tp)
        return y_l

    if mcx is not None:
        bs = mcx.bspec(T)
        y = shard_map(
            shard_body,
            mesh=mcx.mesh,
            in_specs=(P(bs, None), P(bs, None), P(bs, None),
                      P(mcx.tp, None, None), P(mcx.tp, None, None),
                      P(mcx.tp, None, None)),
            out_specs=P(bs, None),
        )(xt, top_p, top_e, p["w_gate"], p["w_up"], p["w_down"])
    else:
        y = shard_body(xt, top_p, top_e, p["w_gate"], p["w_up"], p["w_down"])

    y = y.reshape(B, S, d)
    if "ws_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["ws_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["ws_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = y + jnp.einsum("bsf,fd->bsd", h, p["ws_down"])
    return y, aux


def _moe_a2a(p, xt, cfg, mcx: MeshCtx):
    """all_to_all expert-parallel dispatch (perf opt, cfg.moe_dispatch='a2a').

    The whole layer (routing included) runs in one shard_map with tokens
    sharded over DP *and* TP — no replicated routing work and no GSPMD
    guessing around the boundary.  Each shard packs a (tp, E_loc*C, d) send
    buffer addressed by expert-owner shard; all_to_all over TP exchanges
    token payloads point-to-point; expert shards run one grouped matmul; a
    second all_to_all returns outputs to the token owners — replacing the
    (T_dp, d) psum-combine of the baseline path.  Returns (y, aux)."""
    E, k = cfg.num_experts, cfg.top_k
    tp = mcx.tp_size
    E_loc = E // tp
    T, d = xt.shape
    shards = mcx.dp + (mcx.tp,)
    T_loc = T // (mcx.dp_size * tp)
    # per (source shard, expert) capacity
    C = max(1, int(math.ceil(T_loc * k * cfg.capacity_factor / E)))
    xt = mcx.shard(xt, shards, None)

    def body(xt_l, router, wg, wu, wd):
        # ---- local routing (fp32) + aux loss via psum-mean ----
        logits = jnp.einsum("td,de->te", xt_l.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p_l, top_e_l = jax.lax.top_k(probs, k)
        top_p_l = top_p_l / jnp.sum(top_p_l, axis=-1, keepdims=True)
        nsh = mcx.dp_size * tp
        density = jax.lax.pmean(jnp.mean(
            jax.nn.one_hot(top_e_l[:, 0], E), axis=0), shards)
        router_mean = jax.lax.pmean(jnp.mean(probs, axis=0), shards)
        aux = jnp.sum(density * router_mean) * E * cfg.router_aux_coef

        flat_e = top_e_l.reshape(-1)                   # (T_loc*k,)
        flat_p = top_p_l.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_loc), k)
        # slot within the send buffer: experts grouped by owner shard
        order = jnp.argsort(flat_e, stable=True)
        e_s = flat_e[order]
        pos = jnp.arange(T_loc * k) - jnp.searchsorted(e_s, e_s, side="left")
        ok = pos < C
        slot = jnp.where(ok, e_s * C + pos, E * C)
        send = jnp.zeros((E * C + 1, d), xt_l.dtype)
        send = send.at[slot].set(
            jnp.where(ok[:, None], xt_l[flat_t[order]], 0.0), mode="drop")
        send = send[:E * C].reshape(tp, E_loc * C, d)
        recv = jax.lax.all_to_all(send, mcx.tp, split_axis=0, concat_axis=0,
                                  tiled=True)            # (tp, E_loc*C, d)
        # group by local expert: (tp, E_loc, C, d) -> (E_loc, tp*C, d)
        recv = recv.reshape(tp, E_loc, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_loc, tp * C, d)
        out = _expert_ffn(wg, wu, wd, recv)
        out = out.reshape(E_loc, tp, C, d).transpose(1, 0, 2, 3) \
            .reshape(tp, E_loc * C, d)
        back = jax.lax.all_to_all(out, mcx.tp, split_axis=0, concat_axis=0,
                                  tiled=True)            # (tp, E_loc*C, d)
        back = back.reshape(E * C, d)
        gathered = jnp.where(ok[:, None],
                             back[jnp.clip(slot, 0, E * C - 1)], 0.0)
        y_l = jnp.zeros((T_loc, d), xt_l.dtype)
        y_l = y_l.at[flat_t[order]].add(
            gathered * flat_p[order][:, None].astype(xt_l.dtype))
        return y_l, aux

    y, aux = shard_map(
        body, mesh=mcx.mesh,
        in_specs=(P(shards, None), P(None, None),
                  P(mcx.tp, None, None), P(mcx.tp, None, None),
                  P(mcx.tp, None, None)),
        out_specs=(P(shards, None), P()),
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
