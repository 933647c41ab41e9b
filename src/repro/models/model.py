"""Model facade: builds step functions + shardings + abstract specs for every
architecture config.

Public API
----------
``build(cfg, mcx)`` returns a ``Model`` with:
  * ``init_params(rng)``            — real parameters (smoke tests, examples)
  * ``abstract_params()``           — ShapeDtypeStruct pytree (dry-run)
  * ``param_shardings()``           — NamedSharding pytree
  * ``train_step``                  — (params, opt_state, batch, step) -> ...
  * ``prefill_step``                — (params, batch) -> (tokens, caches)
  * ``decode_step``                 — (params, caches, token, pos) -> ...
  * ``input_specs(shape_cfg)``      — abstract inputs for each step kind
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.models.layers import MeshCtx, pad_to
from repro.train import optimizer as OPT


# ---------------------------------------------------------------------------
# vocab-parallel embedding (shard_map: masked local gather + psum)
# ---------------------------------------------------------------------------
def embed(tokens, table, mcx: MeshCtx):
    """tokens (B,S) int32; table (V,d) sharded P(tp, None) -> (B,S,d)."""
    def inner(tok, tab):
        V_loc = tab.shape[0]
        lo = jax.lax.axis_index(mcx.tp) * V_loc
        idx = tok - lo
        ok = jnp.logical_and(idx >= 0, idx < V_loc)
        x = jnp.where(ok[..., None], tab[jnp.clip(idx, 0, V_loc - 1)], 0)
        return jax.lax.psum(x, mcx.tp)

    bs = mcx.bspec(tokens.shape[0])
    if table.shape[0] % mcx.tp_size:
        # vocab not divisible by TP: plain (replicated-table) gather
        return table[tokens]
    return shard_map(
        inner, mesh=mcx.mesh,
        in_specs=(P(bs, None), P(mcx.tp, None)),
        out_specs=P(bs, None, None),
    )(tokens, table)


# ---------------------------------------------------------------------------
# chunked vocab-parallel cross-entropy (never materializes (B,S,V))
# ---------------------------------------------------------------------------
def ce_loss(h, unemb_t, targets, mask, cfg, mcx: MeshCtx):
    """h: (B,S,d) final-normed; unemb_t: (V,d) [vocab-major]; targets (B,S).
    Returns (sum_loss, sum_mask)."""
    B, S, d = h.shape
    c = min(cfg.loss_chunk, S)
    if S % c:
        pad = c - S % c
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        S = S + pad
    nc = S // c
    hc = jnp.moveaxis(h.reshape(B, nc, c, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, nc, c), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, nc, c), 1, 0)

    V_pad = unemb_t.shape[0]
    pad_mask = (jnp.arange(V_pad) >= cfg.vocab_size)

    def chunk(carry, xs):
        hb, tb, mb = xs
        logits = jnp.einsum("bcd,vd->bcv", hb, unemb_t,
                            preferred_element_type=jnp.float32)
        logits = mcx.shard(logits, mcx.bspec(B), None, mcx.tp)
        logits = jnp.where(pad_mask, -1e30, logits)
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
        onehot = jax.nn.one_hot(tb, logits.shape[-1], dtype=logits.dtype)
        lab = jnp.sum(logits * onehot, axis=-1)
        loss = jnp.sum((lse - lab) * mb)
        return carry + loss, None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (hc, tc, mc))
    return total, jnp.sum(mask)


def logits_fn(h, unemb_t, cfg, mcx):
    """Full logits for decode (h: (B,1,d)) -> (B,V) fp32."""
    logits = jnp.einsum("bsd,vd->bsv", h, unemb_t,
                        preferred_element_type=jnp.float32)
    pad_mask = (jnp.arange(unemb_t.shape[0]) >= cfg.vocab_size)
    return jnp.where(pad_mask, -1e30, logits[:, 0])


def _unemb_t(params, cfg):
    """Vocab-major unembedding matrix (V, d)."""
    if cfg.tie_embeddings:
        return params["emb"]
    return params["unemb"].T


# ---------------------------------------------------------------------------
# Model bundle
# ---------------------------------------------------------------------------
@dataclass
class Model:
    cfg: ModelConfig
    mcx: MeshCtx
    opt_cfg: OPT.OptConfig

    # ---------------- params ------------------------------------------------
    def init_params(self, rng):
        return T.init_stack(self.cfg, rng, self.mcx)

    def abstract_params(self):
        return jax.eval_shape(
            lambda r: T.init_stack(self.cfg, r, self.mcx),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

    def param_specs(self):
        ap = self.abstract_params()
        return tree_param_specs(ap, self.cfg, self.mcx)

    def param_shardings(self):
        return jax.tree.map(lambda s: NamedSharding(self.mcx.mesh, s),
                            self.param_specs())

    def abstract_opt_state(self):
        return jax.eval_shape(
            lambda p: OPT.init_opt_state(p, self.opt_cfg),
            self.abstract_params())

    def opt_shardings(self):
        specs = self.param_specs()
        shapes = jax.tree.map(lambda x: x.shape, self.abstract_params())
        return OPT.opt_state_shardings(specs, shapes, self.mcx, self.opt_cfg)

    # ---------------- embedding / io ---------------------------------------
    def _embed_inputs(self, params, batch):
        cfg, mcx = self.cfg, self.mcx
        if cfg.input_mode == "embeddings":
            x = batch["embeddings"].astype(jnp.dtype(cfg.dtype))
        else:
            x = embed(batch["tokens"], params["emb"], mcx)
        return mcx.shard(x, mcx.dp, None, None)

    # ---------------- train step -------------------------------------------
    def loss_fn(self, params, batch):
        cfg, mcx = self.cfg, self.mcx
        x = self._embed_inputs(params, batch)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, aux = T.forward_train(params, x, cfg, mcx, positions)
        h = L.apply_norm(params["ln_final"], h, cfg)
        unemb_t = _unemb_t(params, cfg)
        labels = batch["labels"]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        total, denom = ce_loss(h, unemb_t, labels, mask, cfg, mcx)
        loss = total / jnp.maximum(denom, 1.0)

        if cfg.mtp_depth and "mtp" in params and cfg.input_mode == "tokens":
            # multi-token prediction: predict t+2 from [h_t ; emb(label_t)]
            mp = params["mtp"]
            e_next = embed(labels, params["emb"], mcx)
            hcat = jnp.concatenate(
                [L.apply_norm(mp["ln_h"], h, cfg),
                 L.apply_norm(mp["ln_e"], e_next, cfg)], axis=-1)
            h2 = jnp.einsum("bsd,de->bse", hcat, mp["proj"])
            y = T.attn_block_fwd(mp["layer"], h2, cfg, mcx, positions,
                                 causal=True)
            y = y[0] if isinstance(y, tuple) else y
            labels2 = jnp.roll(labels, -1, axis=1)
            mask2 = mask.at[:, -1].set(0.0)
            t2, d2 = ce_loss(L.apply_norm(params["ln_final"], y, cfg),
                             unemb_t, labels2, mask2, cfg, mcx)
            loss = loss + 0.3 * t2 / jnp.maximum(d2, 1.0)

        loss = loss + aux
        return loss, {"ce": total / jnp.maximum(denom, 1.0)}

    def train_step(self, params, opt_state, batch, step):
        cfg = self.cfg
        M = cfg.microbatches
        if M == 1:
            (loss, met), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(params, batch)
        else:
            # fp32 grad accumulator is ZeRO-sharded over DP (reduce-scatter
            # per microbatch instead of holding a TP-only-sharded replica)
            specs = self.param_specs()
            shapes = jax.tree.map(lambda x: x.shape, self.abstract_params())
            acc_sh = jax.tree.map(
                lambda s, sh: NamedSharding(
                    self.mcx.mesh,
                    OPT.zero1_spec(s, sh, self.mcx.dp, self.mcx.dp_size)),
                specs, shapes)

            def micro(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(
                    self.loss_fn, has_aux=True)(params, mb)
                gacc = jax.tree.map(
                    lambda a, b, sh: jax.lax.with_sharding_constraint(
                        a + b.astype(jnp.float32), sh),
                    gacc, g, acc_sh)
                return (gacc, lacc + l), None

            g0 = jax.tree.map(
                lambda p, sh: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, jnp.float32), sh), params, acc_sh)
            mbs = jax.tree.map(
                lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch)
            (grads, loss), _ = jax.lax.scan(micro, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / M, grads)
            loss = loss / M
            met = {"ce": loss}

        new_params, new_opt, stats = OPT.apply_updates(
            grads, opt_state, params, step, self.opt_cfg)
        metrics = {"loss": loss, **met, **stats}
        return new_params, new_opt, metrics

    # ---------------- prefill / decode -------------------------------------
    def prefill_step(self, params, batch):
        cfg, mcx = self.cfg, self.mcx
        x = self._embed_inputs(params, batch)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, caches = T.forward_prefill(params, x, cfg, mcx, positions)
        h = L.apply_norm(params["ln_final"], h, cfg)
        logits = logits_fn(h[:, -1:], _unemb_t(params, cfg), cfg, mcx)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, caches

    def decode_step(self, params, caches, token, pos):
        """token: (B,) int32 (or (B,1,d) embeddings); pos: scalar int32."""
        cfg, mcx = self.cfg, self.mcx
        if cfg.input_mode == "embeddings":
            x = token.astype(jnp.dtype(cfg.dtype))
        else:
            x = embed(token[:, None], params["emb"], mcx)
        h, caches = T.forward_decode(params, x, caches, pos, cfg, mcx)
        h = L.apply_norm(params["ln_final"], h, cfg)
        logits = logits_fn(h, _unemb_t(params, cfg), cfg, mcx)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, caches

    # ---------------- abstract inputs ---------------------------------------
    def input_specs(self, shape: ShapeConfig):
        cfg, mcx = self.cfg, self.mcx
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        if shape.kind == "train":
            batch = {"labels": jax.ShapeDtypeStruct((B, S), i32)}
            if cfg.input_mode == "embeddings":
                batch["embeddings"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
            else:
                batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            return {"batch": batch}
        if shape.kind == "prefill":
            batch = {}
            if cfg.input_mode == "embeddings":
                batch["embeddings"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
            else:
                batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            return {"batch": batch}
        # decode
        caches = self.cache_specs(shape)
        if cfg.input_mode == "embeddings":
            token = jax.ShapeDtypeStruct((B, 1, cfg.d_model), dt)
        else:
            token = jax.ShapeDtypeStruct((B,), i32)
        return {"caches": caches, "token": token,
                "pos": jax.ShapeDtypeStruct((), i32)}

    def batch_shardings(self, specs):
        mcx = self.mcx

        def shard_of(path_leaf):
            ndim = len(path_leaf.shape)
            if ndim == 0:
                return NamedSharding(mcx.mesh, P())
            bs = mcx.bspec(path_leaf.shape[0])
            return NamedSharding(mcx.mesh, P(bs, *([None] * (ndim - 1))))
        return jax.tree.map(shard_of, specs)

    # ---------------- caches -------------------------------------------------
    def cache_specs(self, shape: ShapeConfig):
        cfg, mcx = self.cfg, self.mcx
        B, S = shape.global_batch, shape.seq_len
        dt = jnp.dtype(cfg.dtype)
        Lr = cfg.num_layers
        if cfg.family == "ssm":
            K, di, N = cfg.ssm_conv, cfg.d_inner, cfg.ssm_state
            return {"ssm": (jax.ShapeDtypeStruct((Lr, B, K - 1, di), dt),
                            jax.ShapeDtypeStruct((Lr, B, di, N), jnp.float32))}
        if cfg.family == "hybrid":
            K = cfg.ssm_conv
            conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
            n_slots = len(T.hybrid_attn_slots(cfg))
            return {
                "ssm": (jax.ShapeDtypeStruct((Lr, B, K - 1, conv_dim), dt),
                        jax.ShapeDtypeStruct(
                            (Lr, B, cfg.ssm_nheads, cfg.ssm_head_dim,
                             cfg.ssm_state), jnp.float32)),
                "k": jax.ShapeDtypeStruct(
                    (n_slots, B, S, cfg.num_kv_heads, cfg.head_dim), dt),
                "v": jax.ShapeDtypeStruct(
                    (n_slots, B, S, cfg.num_kv_heads, cfg.head_dim), dt),
            }
        if cfg.attn_type == "mla":
            return {
                "c_kv": jax.ShapeDtypeStruct((Lr, B, S, cfg.kv_lora_rank), dt),
                "k_rope": jax.ShapeDtypeStruct((Lr, B, S, cfg.qk_rope_dim), dt),
            }
        return {
            "k": jax.ShapeDtypeStruct(
                (Lr, B, S, cfg.num_kv_heads, cfg.head_dim), dt),
            "v": jax.ShapeDtypeStruct(
                (Lr, B, S, cfg.num_kv_heads, cfg.head_dim), dt),
        }

    def cache_shardings(self, shape: ShapeConfig):
        cfg, mcx = self.cfg, self.mcx
        bs = mcx.bspec(shape.global_batch)

        def rule(leaf):
            nd = len(leaf.shape)
            if nd == 4 and cfg.family == "ssm":
                # (L,B,K-1,di) conv or (L,B,di,N) state: shard di over tp
                if leaf.shape[-1] == cfg.d_inner:
                    return P(None, bs, None, mcx.tp)
                return P(None, bs, mcx.tp, None)
            if nd == 5:   # (L,B,S,KV,hd) attention cache -> seq-shard over tp
                return P(None, bs, mcx.tp, None, None)
            if nd == 4:   # (L,B,S,kvr) mla cache / hybrid conv
                if cfg.attn_type == "mla":
                    return P(None, bs, mcx.tp, None)
                return P(None, bs, None, None)
            return P(*([None] + [bs] + [None] * (nd - 2)))

        specs = self.cache_specs(shape)

        def to_sharding(leaf):
            return NamedSharding(mcx.mesh, rule(leaf))
        return jax.tree.map(to_sharding, specs)


# ---------------------------------------------------------------------------
# parameter sharding rules (by tree path)
# ---------------------------------------------------------------------------
def _spec_for_leaf(path_names, full_shape, cfg, mcx) -> P:
    tp = mcx.tp
    tp_size = mcx.tp_size
    name = path_names[-1]
    in_moe = "moe" in path_names
    in_ssm = "ssm" in path_names
    # leaves under "stacks" carry a leading layer dim: apply rules to shape[1:]
    stacked = "stacks" in path_names
    leaf_shape = full_shape[1:] if stacked else full_shape
    nd = len(full_shape)

    def fits(dim):
        return leaf_shape[dim] % tp_size == 0

    base: Optional[tuple] = None
    if name == "emb":
        base = (tp, None) if fits(0) else (None, None)
    elif name == "unemb":
        base = (None, tp) if fits(1) else (None, None)
    elif name in ("wq", "wk", "wv"):
        base = (None, tp, None) if fits(1) else (None, None, None)
    elif name == "wo":
        base = (tp, None, None) if fits(0) else (None, None, None)
    elif name in ("bq",):
        base = (tp, None) if fits(0) else (None, None)
    elif name in ("bk", "bv"):
        base = (None, None)
    elif name in ("wq_b", "wk_b", "wv_b"):
        base = (None, tp, None) if fits(1) else (None, None, None)
    elif name in ("wq_a", "wkv_a"):
        base = (None, None)
    elif name in ("w_gate", "w_up"):
        if in_moe:  # (E, d, ff): shard experts
            base = (tp, None, None) if fits(0) else (None, None, None)
        else:
            base = (None, tp) if fits(1) else (None, None)
    elif name == "w_down":
        if in_moe:
            base = (tp, None, None) if fits(0) else (None, None, None)
        else:
            base = (tp, None) if fits(0) else (None, None)
    elif name in ("ws_gate", "ws_up"):
        base = (None, tp) if fits(1) else (None, None)
    elif name == "ws_down":
        base = (tp, None) if fits(0) else (None, None)
    elif name == "b_up":
        base = (tp,) if fits(0) else (None,)
    elif name == "router":
        base = (None, None)
    elif in_ssm and cfg.ssm_version == 1:
        if name == "in_proj":
            base = (None, tp) if fits(1) else (None, None)
        elif name == "conv_w":
            base = (None, tp) if fits(1) else (None, None)
        elif name in ("conv_b", "dt_bias", "D"):
            base = (tp,) if fits(0) else (None,)
        elif name in ("x_proj", "A_log", "out_proj"):
            base = (tp, None) if fits(0) else (None, None)
        elif name == "dt_proj":
            base = (None, tp) if fits(1) else (None, None)
    elif in_ssm and cfg.ssm_version == 2:
        # mamba2 projections have heterogeneous concat segments: replicate
        base = tuple([None] * nd)

    if base is None:
        base = tuple([None] * len(leaf_shape))
    # stacked layers: leading layer dim is never sharded
    if len(base) < nd:
        base = tuple([None] * (nd - len(base))) + base
    # FSDP (ZeRO-3): additionally shard the largest unsharded dim over DP;
    # GSPMD re-gathers each layer's slice inside the scan body on use.
    if cfg.fsdp and nd >= 2:
        from repro.train.optimizer import zero1_spec
        return zero1_spec(P(*base), full_shape, mcx.dp, mcx.dp_size)
    return P(*base)


def tree_param_specs(abstract_params, cfg, mcx):
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [walk(v, path + (str(i),)) for i, v in enumerate(node)]
            return type(node)(t) if not isinstance(node, tuple) else tuple(t)
        return _spec_for_leaf(path, node.shape, cfg, mcx)
    return walk(abstract_params, ())


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------
def build(cfg: ModelConfig, mcx: MeshCtx,
          opt_cfg: Optional[OPT.OptConfig] = None) -> Model:
    oc = opt_cfg or OPT.OptConfig(grad_compress=cfg.grad_compress)
    return Model(cfg=cfg, mcx=mcx, opt_cfg=oc)
