"""Mixture-of-experts transformer LM — expert parallelism end-to-end.

Expert parallelism is a TPU-native extension beyond the reference
(SURVEY.md §2.5 lists EP as absent). Every block's MLP is a top-1 switch
MoE (``ops/moe.switch_moe``): expert weights shard over the 'shard' mesh
axis via Model.param_specs overrides, tokens dispatch/combine with
all_to_all, and the router's load-balancing auxiliary loss joins the
objective.

Which entry of ``ops/moe`` drops: this model's, ``switch_moe``. Under a
mesh it DROPS the (token, choice) slots past ``capacity_factor`` (and
reports the share as ``moe_dropped``); on one device it takes the dense
path, which drops nothing but computes EVERY expert for EVERY token, so
it is for small sizes only. The dropless layer, ``ops/moe.routed_experts``
(work in proportion to the rows routed to the experts a chip holds,
gated experts), is ``models/keye_vl2``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from parallax_tpu.core.engine import Model
from parallax_tpu.core.mesh import AXIS_SHARD
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops.ring_attention import full_attention_reference


@dataclasses.dataclass
class MoeLMConfig:
    vocab_size: int = 32000
    model_dim: int = 512
    num_heads: int = 8
    expert_dim: int = 1024
    num_experts: int = 16
    num_layers: int = 6
    max_len: int = 1024
    capacity_factor: float = 1.25
    # 1 = switch routing; 2 = GShard top-2 (renormalized gates,
    # first-choice capacity priority)
    top_k: int = 1
    aux_loss_weight: float = 0.01
    use_pallas_attention: bool = False
    learning_rate: float = 3e-4
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)


def tiny_config(**kw) -> MoeLMConfig:
    defaults = dict(vocab_size=512, model_dim=32, num_heads=2,
                    expert_dim=64, num_experts=8, num_layers=2,
                    max_len=32)
    defaults.update(kw)
    return MoeLMConfig(**defaults)


def build_model(cfg: MoeLMConfig) -> Model:
    V, D, E, F = (cfg.padded_vocab, cfg.model_dim, cfg.num_experts,
                  cfg.expert_dim)
    dt = cfg.compute_dtype

    def dense_init(rng, shape, axis=0):
        return jax.random.normal(rng, shape) * (1.0 / np.sqrt(shape[axis]))

    def init_fn(rng):
        ks = jax.random.split(rng, 3 + cfg.num_layers)
        blocks = []
        for i in range(cfg.num_layers):
            bk = jax.random.split(ks[3 + i], 5)
            blocks.append({
                "wqkv": dense_init(bk[0], (D, 3 * D)),
                "wo": dense_init(bk[1], (D, D)),
                "router": dense_init(bk[2], (D, E)),
                "moe_w1": dense_init(bk[3], (E, D, F), axis=1),
                "moe_w2": dense_init(bk[4], (E, F, D), axis=1),
                "ln1": {"s": jnp.ones((D,)), "b": jnp.zeros((D,))},
                "ln2": {"s": jnp.ones((D,)), "b": jnp.zeros((D,))},
            })
        return {
            "emb": jax.random.normal(ks[0], (V, D)) * 0.02,
            "pos": jax.random.normal(ks[1], (cfg.max_len, D)) * 0.02,
            "out_w": dense_init(ks[2], (D, V)),
            "blocks": blocks,
        }

    def layer_norm(x, p):
        m = jnp.mean(x, -1, keepdims=True)
        v = jnp.var(x, -1, keepdims=True)
        return ((x - m) * jax.lax.rsqrt(v + 1e-6) * p["s"].astype(x.dtype)
                + p["b"].astype(x.dtype))

    def attention(x, p):
        B, T, _ = x.shape
        q, k, v = jnp.split(x @ p["wqkv"].astype(dt), 3, -1)
        Hn = cfg.num_heads

        def heads(z):
            return z.reshape(B, T, Hn, D // Hn)

        if cfg.use_pallas_attention:
            from parallax_tpu.ops.pallas_attention import flash_attention
            out = flash_attention(heads(q), heads(k), heads(v),
                                  causal=True)
        else:
            out = full_attention_reference(heads(q), heads(k), heads(v),
                                           causal=True)
        return out.reshape(B, T, D) @ p["wo"].astype(dt)

    def loss_fn(params, batch, rng):
        ids = batch["ids"]
        B, T = ids.shape
        mesh = emb_ops.current_mesh()
        x = emb_ops.embedding_lookup(params["emb"], ids).astype(dt)
        x = x + params["pos"][:T].astype(dt)[None]
        aux_total, drop_total = 0.0, 0.0
        for p in params["blocks"]:
            x = layer_norm(x + attention(x, p), p["ln1"])
            tokens = x.reshape(B * T, D)
            moe_out, aux, dropped = moe_ops.switch_moe(
                tokens, p["router"], p["moe_w1"], p["moe_w2"], mesh,
                cfg.capacity_factor, top_k=cfg.top_k)
            aux_total = aux_total + aux
            drop_total = drop_total + dropped
            x = layer_norm(x + moe_out.reshape(B, T, D).astype(dt),
                           p["ln2"])
        logits = x.astype(jnp.float32) @ params["out_w"]
        logits = emb_ops.mask_padded_logits(logits, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.zeros((B, 1), ids.dtype)], axis=1)
        w = jnp.concatenate(
            [jnp.ones((B, T - 1)), jnp.zeros((B, 1))], axis=1).reshape(-1)
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.reshape(B * T, V), labels.reshape(B * T))
        lm_loss = jnp.sum(nll * w) / jnp.sum(w)
        aux_mean = aux_total / cfg.num_layers
        loss = lm_loss + cfg.aux_loss_weight * aux_mean
        # surface capacity overflow as a metric — silent token drops
        # corrupt training with no signal otherwise
        return loss, {"lm_loss": lm_loss, "aux_loss": aux_mean,
                      "moe_dropped": drop_total / cfg.num_layers}

    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adam(cfg.learning_rate))
    return Model(
        init_fn, loss_fn, optimizer=tx,
        param_specs={
            "blocks/*/moe_w1": P(AXIS_SHARD, None, None),
            "blocks/*/moe_w2": P(AXIS_SHARD, None, None),
        })


def make_batch(rng: np.random.Generator, batch_size: int, seq_len: int,
               vocab_size: int):
    return {"ids": rng.integers(1, vocab_size,
                                (batch_size, seq_len)).astype(np.int32)}


# ----- KV-cached serving decode -------------------------------------------
# Incremental decode for the post-LN switch-MoE blocks above, consumed
# by serve/adapters.MoeLMDecodeProgram. Same construction as
# models/long_context's serve section (whose attention/LN helpers this
# reuses — identical math), but: attention consumes the RAW block input
# (post-LN residual order), and each block's MLP is the switch MoE.
# Without a mesh, ops/moe.switch_moe takes the dense per-token expert
# path — row-wise with no capacity drops, so slots stay independent and
# exact-under-greedy holds. Under a live mesh the capacity-bounded
# all_to_all dispatch is NOT row-independent (a co-batched slot can
# displace another's token at capacity) — documented serving caveat.

from parallax_tpu.models.long_context import (_prefill_finish,  # noqa: E402
                                              _serve_attention,
                                              _serve_layer_norm)


def _prefill_embed(cfg: MoeLMConfig, params, ids):
    """Prefill chunk 0: embedding + positional add over the padded
    prompt buffer ``ids`` [1, Ts]; allocates the K/V capture stacks."""
    dt = cfg.compute_dtype
    Ts = ids.shape[1]
    x = (emb_ops.embedding_lookup(params["emb"], ids).astype(dt)
         + params["pos"][:Ts].astype(dt)[None])
    z = jnp.zeros((cfg.num_layers, 1, Ts, cfg.model_dim), dt)
    return {"x": x, "pk": z, "pv": z, "ids": ids}


def _prefill_layers(cfg: MoeLMConfig, params, carry, lo, hi):
    """Prefill layers ``[lo, hi)``: capture each layer's prompt K/V
    projections (of the RAW block input), then apply the post-LN MoE
    block. Padded rows route through the MoE too (garbage, dropped by
    the serve insert's sentinel mask)."""
    dt = cfg.compute_dtype
    x, pk, pv = carry["x"], carry["pk"], carry["pv"]
    B, Ts, D = x.shape
    Hn = cfg.num_heads
    mesh = emb_ops.current_mesh()

    def heads(z):
        return z.reshape(B, Ts, Hn, D // Hn)

    for i in range(lo, hi):
        p = params["blocks"][i]
        q, k, v = jnp.split(x @ p["wqkv"].astype(dt), 3, -1)
        pk = pk.at[i].set(k)
        pv = pv.at[i].set(v)
        out = full_attention_reference(heads(q), heads(k), heads(v),
                                       causal=True)
        x = _serve_layer_norm(x + out.reshape(B, Ts, D) @ p["wo"].astype(dt),
                         p["ln1"])
        moe_out, _, _ = moe_ops.switch_moe(
            x.reshape(B * Ts, D), p["router"], p["moe_w1"], p["moe_w2"],
            mesh, cfg.capacity_factor, top_k=cfg.top_k)
        x = _serve_layer_norm(x + moe_out.reshape(B, Ts, D).astype(dt),
                         p["ln2"])
    return {"x": x, "pk": pk, "pv": pv, "ids": carry["ids"]}


def _decode_step_cached(cfg: MoeLMConfig, params, tok, t, base, first,
                        kc, vc, pages=None, page_size=None,
                        attn_impl=None):
    """One batched cached decoder step (see long_context's docstring for
    the row contract): post-LN blocks, switch-MoE MLP routed per token
    at S tokens, padded-vocab logits masked before the argmax."""
    dt = cfg.compute_dtype
    D = cfg.model_dim
    S = tok.shape[0]
    mesh = emb_ops.current_mesh()
    paged = pages is not None
    if paged:
        from parallax_tpu.ops import pallas_paged_attention as _ppa
        pool, ps = kc.shape[1], int(page_size)
        Tbuf = pages.shape[1] * ps
        impl = _ppa.resolve_impl(
            attn_impl, G=1, D=D, page_size=ps,
            num_heads=cfg.num_heads,
            itemsize=jnp.dtype(dt).itemsize)
    else:
        Tbuf = kc.shape[2]
        rows = jnp.arange(S)
    tok_eff = jnp.where(t == 0, first, tok)
    pos = (base + t)[:, None]                                # [S, 1]
    pos_emb = jnp.take(params["pos"].astype(dt), pos, axis=0,
                       mode="clip")                          # [S, 1, D]
    x = (emb_ops.embedding_lookup(params["emb"],
                                  tok_eff[:, None]).astype(dt)
         + pos_emb)                                          # [S, 1, D]
    mask = (jnp.arange(Tbuf)[None, :] <= pos)[:, None, None, :]
    if paged:
        pg, off = _ppa.sentinel_write_coords(pages, pos, ps, pool)
    for i, p in enumerate(params["blocks"]):
        q, k_t, v_t = jnp.split(x @ p["wqkv"].astype(dt), 3, -1)
        if paged:
            kc = kc.at[i, pg, off].set(k_t, mode="drop")
            vc = vc.at[i, pg, off].set(v_t, mode="drop")
            if impl == "kernel":
                y = _ppa.paged_decode_attention(
                    q, kc[i], vc[i], pages, pos,
                    num_heads=cfg.num_heads, page_size=ps,
                    impl="kernel")
            else:
                k_all = _ppa.paged_gather(kc[i], pages)
                v_all = _ppa.paged_gather(vc[i], pages)
                y = _serve_attention(q, k_all, v_all, mask,
                                     cfg.num_heads)
        else:
            kc = kc.at[i, rows[:, None], pos].set(k_t, mode="drop")
            vc = vc.at[i, rows[:, None], pos].set(v_t, mode="drop")
            y = _serve_attention(q, kc[i], vc[i], mask, cfg.num_heads)
        x = _serve_layer_norm(x + y @ p["wo"].astype(dt), p["ln1"])
        moe_out, _, _ = moe_ops.switch_moe(
            x.reshape(S, D), p["router"], p["moe_w1"], p["moe_w2"],
            mesh, cfg.capacity_factor, top_k=cfg.top_k)
        x = _serve_layer_norm(x + moe_out.reshape(S, 1, D).astype(dt),
                         p["ln2"])
    logits = x[:, 0].astype(jnp.float32) @ params["out_w"]
    return emb_ops.mask_padded_logits(logits, cfg.vocab_size), kc, vc


def _init_serve_self_cache(cfg: MoeLMConfig, batch: int, max_len: int):
    z = jnp.zeros((cfg.num_layers, batch, max_len, cfg.model_dim),
                  cfg.compute_dtype)
    return z, z


def _init_serve_paged_cache(cfg: MoeLMConfig, pool_pages: int,
                            page_size: int):
    z = jnp.zeros((cfg.num_layers, pool_pages, page_size,
                   cfg.model_dim), cfg.compute_dtype)
    return z, z
