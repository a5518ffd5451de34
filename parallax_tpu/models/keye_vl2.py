"""The language model of Keye-VL-2.0-30B-A3B — sparse attention over
GQA, dropless routed experts, a chip's share of an expert-parallel
layer.

Every layer is the same pre-norm block (``config.json``:
``decoder_sparse_step`` 1, ``mlp_only_layers`` []): RMSNorm, grouped-
query attention (32 query heads on 4 key/value heads of 128) with
QK-norm and RoPE in three position streams (``mrope_section``), read
through a learned top-k key selection (``sa_config``: an indexer of 16
heads x 64 with one shared key head picks ``topk`` 2048 keys a query;
``ops/sparse_attention.py``); RMSNorm, a router over all 128 experts,
top-8 with renormalised gates (``ops/moe.linear_router``), and the
SwiGLU experts this chip holds (``ops/moe.routed_experts``:
``experts_held`` experts from ``first_expert`` on, dropless). Untied head. The vision tower is not
built: the published config gives none of its sizes, so batches are
text and carry one position for all three streams (a batch may bring
its own ``pos [B, T, 3]``).

The chip's share (``docs``: ``PERF.md`` section 4): a layer of 128
experts is 10 GB of training state, so the layer is shared by the chips
of an expert-parallel group and a chip holds ``experts_held`` experts
and its slice of the vocabulary. What the absent experts would add to
a token is left out, and that partial result is what the next layer
reads. On one device ``first_expert`` is a field; the exchange that
brings other chips' tokens is not part of this model.

Training: the embedding is a gather-only table on the engine's slices
path (``SliceAdam``); everything else Adam behind a global-norm clip;
bfloat16 compute on float32 weights, with the router, the indexer's
scores, every softmax and every norm's statistics in float32; each
layer rematerialised, the layers under one ``lax.scan`` over their
stacked parameters, whose matrices are cast to bfloat16 before the loop
(``models/decoder.in_compute_dtype``). The loss is
the cross-entropy plus ``router_aux_loss_coef`` x the load-balance loss
plus ``indexer_loss_weight`` x the indexer's KL loss, which alone
reaches the indexer's weights (its input is cut off by
``stop_gradient``).

Batch contract as ``models/lm1b``: ``x``, ``y`` int32 ``[B, T]``, ``w``
float weights.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.engine import Model
from parallax_tpu.models import decoder
from parallax_tpu.models.decoder import (
    clipped_adam, in_compute_dtype, lm_head_nll, normal_init, rms_norm,
    weighted_mean)
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops import sparse_attention as sa_ops


@dataclasses.dataclass
class KeyeVL2Config:
    vocab_size: int = 151936
    model_dim: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    rms_norm_eps: float = 1e-6
    # sa_config: the indexer and its selection
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    q_chunk_size: int = 512
    # the experts: the router is num_experts wide whatever is held here
    num_experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 768
    experts_held: int = 128
    first_expert: int = 0
    seq_len: int = 8192
    router_aux_loss_coef: float = 0.001
    indexer_loss_weight: float = 1.0
    learning_rate: float = 3e-4
    # steps over which the learning rate rises linearly from 0 (Adam's
    # first steps at the full rate move every weight by it at once)
    warmup_steps: int = 0
    max_grad_norm: float = 1.0
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)


# the layers' leaves that a block multiplies in the compute dtype
MATRICES = ("wq", "wk", "wv", "wo", "idx_wq", "idx_wk", "idx_ww", "w_gate",
            "w_up", "w_down")


def tiny_config(**kw) -> KeyeVL2Config:
    defaults = dict(vocab_size=96, model_dim=32, num_layers=2, num_heads=4,
                    num_kv_heads=2, head_dim=16, mrope_section=(2, 2, 4),
                    indexer_heads=2, indexer_head_dim=8, indexer_topk=6,
                    q_chunk_size=4, num_experts=8, experts_per_token=2,
                    expert_dim=16, experts_held=4, first_expert=0,
                    seq_len=16, num_partitions=1,
                    compute_dtype=jnp.float32)
    defaults.update(kw)
    return KeyeVL2Config(**defaults)


def rope3(x, pos, theta: float, section):
    """Rotary embedding in three position streams: ``x [B, T, ..., 2n]``
    in half-split layout, pair ``i`` turning by ``pos[..., stream(i)] *
    theta^(-i / n)``. ``section`` counts each stream's pairs and is
    scaled to ``n`` (the indexer's heads have half the pairs of the
    attention's). Angles in float32."""
    n = x.shape[-1] // 2
    total = sum(section)
    counts = [c * n // total for c in section]
    if sum(counts) != n:
        raise ValueError(f"mrope_section {tuple(section)} does not scale "
                         f"to {n} pairs")
    stream = np.repeat(np.arange(3), counts)
    inv_freq = jnp.asarray(
        float(theta) ** (-np.arange(n, dtype=np.float64) / n), jnp.float32)
    angle = pos[..., stream].astype(jnp.float32) * inv_freq    # [B, T, n]
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (n,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1 = x[..., :n].astype(jnp.float32)
    x2 = x[..., n:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _layer(cfg: KeyeVL2Config, p, h, pos, impls=(None, None),
           collect: bool = False):
    """One block on ``h [B, T, D]``. Returns the new ``h``, the layer's
    scalars, and with ``collect`` what it selected. ``impls``: the
    executors of the attention and of the experts' products (None: by
    the backend; ``ops/sparse_attention``, ``ops/moe``)."""
    dt = cfg.compute_dtype
    B, T, D = h.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
    eps, theta, section = cfg.rms_norm_eps, cfg.rope_theta, cfg.mrope_section

    # the layers' names in the compiled step (obs/xprof.LAYER_SCOPES);
    # the indexer's, being the inner one, wins inside the attention's
    with jax.named_scope("attention"):
        x = rms_norm(h, p["ln1"], eps)
        q = rms_norm((x @ p["wq"].astype(dt)).reshape(B, T, Hq, Dh),
                     p["q_norm"], eps)
        k = rms_norm((x @ p["wk"].astype(dt)).reshape(B, T, Hkv, Dh),
                     p["k_norm"], eps)
        v = (x @ p["wv"].astype(dt)).reshape(B, T, Hkv, Dh)
        q, k = rope3(q, pos, theta, section), rope3(k, pos, theta, section)
        with jax.named_scope("indexer"):
            # the indexer learns from its own loss alone
            xi = jax.lax.stop_gradient(x)
            qi = rope3((xi @ p["idx_wq"].astype(dt)).reshape(B, T, Hi, Di),
                       pos, theta, section)
            ki = rope3(xi @ p["idx_wk"].astype(dt), pos, theta, section)
            wi = xi @ p["idx_ww"].astype(dt)
        attn = sa_ops.sparse_attention(
            q, k, v, qi, ki, wi, topk=cfg.indexer_topk,
            q_chunk=cfg.q_chunk_size, return_selection=collect,
            impl=impls[0])
        h = h + attn.out.reshape(B, T, Hq * Dh) @ p["wo"].astype(dt)

    with jax.named_scope("moe"):
        y = rms_norm(h, p["ln2"], eps).reshape(B * T, D)
        route = moe_ops.linear_router(y, p["router"], cfg.experts_per_token)
        moe = moe_ops.routed_experts(
            y, route.choice, route.gate, p["w_gate"], p["w_up"],
            p["w_down"], num_experts=cfg.num_experts,
            first_expert=cfg.first_expert, impl=impls[1])
        h = h + moe.out.reshape(B, T, D)
    scalars = {"indexer_loss": attn.indexer_loss, "aux_loss": route.aux_loss,
               "selected": attn.selected, "causal": attn.causal,
               **moe_ops.moe_scalars(moe)}
    extra = ({"selection": attn.selection, "expert_choice": route.choice}
             if collect else None)
    return h, scalars, extra


def _positions(batch, B, T):
    pos = batch.get("pos")
    if pos is None:
        pos = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :, None], (B, T, 3))
    return pos


def build_model(cfg: KeyeVL2Config, impls=(None, None)) -> Model:
    V, D, L = cfg.padded_vocab, cfg.model_dim, cfg.num_layers
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hi, Di = cfg.indexer_heads, cfg.indexer_head_dim
    E, Eh, F = cfg.num_experts, cfg.experts_held, cfg.expert_dim
    moe_ops.check_held(E, Eh, cfg.first_expert)
    dt = cfg.compute_dtype

    def init_fn(rng):
        ks = jax.random.split(rng, 12)
        layers = {
            "ln1": jnp.ones((L, D)), "ln2": jnp.ones((L, D)),
            "q_norm": jnp.ones((L, Dh)), "k_norm": jnp.ones((L, Dh)),
            "wq": normal_init(ks[0], (L, D, Hq * Dh), D),
            "wk": normal_init(ks[1], (L, D, Hkv * Dh), D),
            "wv": normal_init(ks[2], (L, D, Hkv * Dh), D),
            "wo": normal_init(ks[3], (L, Hq * Dh, D), Hq * Dh),
            "idx_wq": normal_init(ks[4], (L, D, Hi * Di), D),
            "idx_wk": normal_init(ks[5], (L, D, Di), D),
            "idx_ww": normal_init(ks[6], (L, D, Hi), D),
            "router": normal_init(ks[7], (L, D, E), D),
            "w_gate": normal_init(ks[8], (L, Eh, D, F), D),
            "w_up": normal_init(ks[9], (L, Eh, D, F), D),
            "w_down": normal_init(ks[10], (L, Eh, F, D), F),
        }
        k_emb, k_head = jax.random.split(ks[11])
        # the embedding at unit scale, so that a token's own row and not
        # the attention's near-uniform mean decides where it is routed
        return {"emb": jax.random.normal(k_emb, (V, D)),
                "layers": layers,
                "final_norm": jnp.ones((D,)),
                "head": normal_init(k_head, (D, V), D)}

    # what a rematerialised layer keeps for its backward pass: each
    # chunk's selection, attention output and logsumexp, and the
    # experts' row buffers (the ops name them), so that no kernel runs a
    # second time
    policy = jax.checkpoint_policies.save_only_these_names(
        sa_ops.KEPT, moe_ops.KEPT)

    def loss_fn(params, batch, rng):
        x = batch["x"]
        B, T = x.shape
        pos = _positions(batch, B, T)
        h = emb_ops.embedding_lookup(params["emb"], x).astype(dt)

        body = jax.checkpoint(
            lambda h, p: _layer(cfg, p, h, pos, impls)[:2], policy=policy)
        # the scan's own operations (the matrices' cast, a layer's
        # weights cut out of the stack, its kept arrays and its gradients
        # written into theirs, the loop) go by this name; inside a block
        # its layers' names win
        with jax.named_scope("layer_scan"):
            h, per_layer = jax.lax.scan(
                body, h, in_compute_dtype(params["layers"], MATRICES, dt))
        s = jax.tree.map(lambda a: jnp.mean(a.astype(jnp.float32)),
                         per_layer)

        lm_loss = weighted_mean(lm_head_nll(
            cfg, h, params["final_norm"], params["head"], batch["y"]), batch)
        indexer_loss = s["indexer_loss"] / (B * T)
        loss = (lm_loss + cfg.router_aux_loss_coef * s["aux_loss"]
                + cfg.indexer_loss_weight * indexer_loss)
        causal_total = jnp.sum(per_layer["causal"])
        return loss, {
            "lm_loss": lm_loss, "aux_loss": s["aux_loss"],
            "indexer_loss": indexer_loss, **moe_ops.moe_metrics(per_layer),
            "attn_selected_share":
                jnp.sum(per_layer["selected"]) / causal_total}

    from parallax_tpu.ops.sparse_optim import SliceAdam
    return Model(init_fn, loss_fn, optimizer=clipped_adam(cfg),
                 slice_updaters={"emb": SliceAdam(cfg.learning_rate)},
                 gauges={**moe_ops.GAUGES,
                         "sparse_attn.indexer_loss": "indexer_loss",
                         "sparse_attn.selected_share":
                             "attn_selected_share"})


def layer_selection(cfg: KeyeVL2Config, params, batch, layer: int = 0,
                    impls=(None, None)):
    """What layer ``layer`` selects on ``batch``, by the model's own
    code: ``{"selection": bool [B, T, T] (the keys each query attends),
    "expert_choice": int [B * T, experts_per_token]}``. For the
    comparison with a reference; not on the training path."""
    x = batch["x"]
    B, T = x.shape
    pos = _positions(batch, B, T)
    h = emb_ops.embedding_lookup(params["emb"], x).astype(cfg.compute_dtype)
    for i in range(layer + 1):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        h, _, extra = _layer(cfg, p, h, pos, impls, collect=(i == layer))
    return extra


# the synthetic batch, Zipf(1.3)
make_batch = functools.partial(decoder.make_batch, zipf=1.3)
