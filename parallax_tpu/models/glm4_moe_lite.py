"""GLM-4.7-Flash (zai-org, ``model_type`` ``glm4_moe_lite``) — multi-head
latent attention (MLA) at heads of 256, a leading dense layer, sigmoid
top-4 of 64 SwiGLU experts chosen under balancing biases beside a shared
expert, and a multi-token-prediction (MTP) block on the shared table and
head; a chip's share of an expert-parallel layer.

``h_0 = emb[x]`` (no scale). Every layer is two sub-layers, each normed
on its input only:

*Attention, every layer* (the training form of MLA). ``a = RMSNorm(h)``;

- ``c_q = RMSNorm_q(a W_qa)`` (``q_lora_rank``); ``q = c_q W_qb`` as
  ``num_heads`` heads of ``[q_nope (qk_nope_head_dim) | q_rope
  (qk_rope_head_dim)]``;
- ``[c_kv | k_r] = a W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``);
  ``c_kv = RMSNorm_kv(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` per head
  (``qk_nope_head_dim`` + ``v_head_dim``);
- ``k_r = RoPE(k_r)`` (half-split, every rotary dim, ``rope_theta``),
  ONE key a position broadcast to every head (its gradient is the sum
  over the heads); ``q_rope = RoPE(q_rope)``;
- ``o = softmax(q k^T / sqrt(head_dim)) v``, causal, with ``q = [q_nope
  | q_rope]``, ``k = [k_nope | k_r]``
  (``ops/pallas_attention.flash_attention`` through
  ``models/decoder.attend``: as many key/value heads as query heads, one
  head size for q . k and v, ``head_dim = qk_nope_head_dim +
  qk_rope_head_dim = v_head_dim``); ``h += o W_o``.

Both low-rank paths, their norms, ``k_r``'s broadcast and RoPE are the
scope ``mla_latent`` inside ``attention``. No QK-norm, no gate, no
output norms.

*Dense layers* (the first ``num_dense_layers``): ``h += SwiGLU(RMSNorm(h))``
at ``dense_mlp_dim`` (``models/decoder.mlp``, the scope ``mlp``).

*Expert layers.* ``m = RMSNorm(h)``; ``s = sigmoid(m W_r)`` over all
``num_experts`` in float32, the choice ``top4(s + b)``, the gates ``s``
of the chosen WITHOUT ``b``, over their sum plus 1e-20 and times
``route_scale`` (``models/decoder.expert_mix``); ``h += shared(m) +
sum_c g_c expert_c(m)`` over the chosen experts held here. The balancing
biases ``b [L_moe + num_mtp_layers, E]`` (the MTP block's last) are
the ``model_state`` of a stateful ``Model`` (``ops/moe.balance_step``): no gradient reaches
them, no auxiliary loss.

*The MTP block* (``num_mtp_layers`` 1, or 0 for none; straight-line
after the loop): from the main stream's last ``h`` (before the final
norm), ``u = [RMSNorm_e(emb[x_{t+1}]) ; RMSNorm_h(h)] W_eh`` (the scope
``mtp``), one more expert layer on ``u``, its own final RMSNorm, and the
SHARED head against ``x_{t+2}``. Its input ids are the batch's ``y``
(``y_t = x_{t+1}``), its labels ``y`` shifted once more, its weights
``w`` shifted likewise with the last position weighing 0: the feed
contract stays ``models/lm1b``'s. The table is looked up twice, for the
stream and for the block, and the engine updates it once a step from
both lookups' rows.

Loss: ``CE(main) + mtp_loss_weight * CE(MTP)``, each the weighted mean
over its positions. The scope ``lm_head`` holds both heads.

The dense layers (``params["dense"]``) run before the loop as straight
line code, the expert layers (``params["layers"]``) under ONE ``lax.scan``,
the MTP block (``params["mtp"]``, one layer's leaves) after it;
all three inside the scope ``layer_scan``. The embedding is a
gather-only table on the engine's slices path (``SliceAdam`` at the
constant ``table_learning_rate``), everything else Adam behind a
global-norm clip; bfloat16 compute on float32 weights, the router, the
softmaxes, the norms' statistics and RoPE's angles in float32; each
layer rematerialised keeping the attention's output and logsumexp and
the experts' row buffers (``KEPT``).

The chip's share (``PERF.md`` section 4): each layer's experts shared by
``experts_held`` of ``num_experts`` a chip, the vocabulary's rows
likewise; what the absent experts would add is left out, and the
shared expert counts once where the shares are added
(``tests/test_glm4_moe_lite.py``).

Batch contract as ``models/lm1b``: ``x``, ``y`` int32 ``[B, T]``, ``w``
float weights; a batch may bring ``expert_choice`` int32 ``[L_moe +
num_mtp_layers, B, T, k]`` (the MTP block's layer last), which then
takes the place of the router's own top-k (a comparison under one
routing; not on the training path).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.engine import Model
from parallax_tpu.models.decoder import (  # noqa: F401
    attend, clipped_adam, expert_mix, in_compute_dtype, lm_head_nll,
    make_batch, mlp, normal_init, rms_norm, rope, weighted_mean)
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops import pallas_attention as pa

# what a rematerialised layer keeps for its backward pass: the
# attention's output and logsumexp and the experts' row buffers, so that
# no kernel runs a second time
KEPT = (pa.KEPT, moe_ops.KEPT)


@dataclasses.dataclass
class GlmConfig:
    vocab_size: int = 154880
    model_dim: int = 2048
    # the dense layers held plus the expert layers (num_hidden_layers);
    # the MTP block is not counted
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    # the leading layers whose MLP is dense, and its width
    num_dense_layers: int = 1
    dense_mlp_dim: int = 10240
    # the experts: the router is num_experts wide whatever is held here
    num_experts: int = 64
    experts_per_token: int = 4
    expert_dim: int = 1536
    experts_held: int = 64
    first_expert: int = 0
    num_shared_experts: int = 1
    # the gates: the chosen scores over their sum (route_norm), times
    # route_scale
    route_norm: bool = True
    route_scale: float = 1.8
    # how far a step moves each balancing bias
    load_balance_coeff: float = 1e-3
    # the multi-token-prediction block (1, or 0 for none) and its loss's
    # weight
    num_mtp_layers: int = 1
    mtp_loss_weight: float = 0.3
    seq_len: int = 8192
    learning_rate: float = 3e-4
    # steps over which the learning rate rises linearly from 0
    warmup_steps: int = 0
    # lazy Adam's constant rate on the table (None: `learning_rate`)
    table_learning_rate: Optional[float] = None
    max_grad_norm: float = 1.0
    # the dense flash kernels' tiles (queries, keys)
    flash_tiles: tuple = (512, 512)
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def head_dim(self) -> int:
        """The flash kernels' one head size: ``q . k`` over the nope and
        the rotary dims (``build_model`` holds ``v`` to it)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# the leaves that a block multiplies in the compute dtype
MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
            "w_down", "shared_w_gate", "shared_w_up", "shared_w_down",
            "w_eh")


def tiny_config(**kw) -> GlmConfig:
    """One dense layer, three expert layers and one MTP block at toy
    widths."""
    defaults = dict(vocab_size=96, model_dim=32, num_layers=4, num_heads=4,
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
                    qk_rope_head_dim=4, v_head_dim=16, rope_theta=100.0,
                    dense_mlp_dim=48,
                    num_experts=8, experts_per_token=2, expert_dim=16,
                    experts_held=4, route_scale=1.5,
                    load_balance_coeff=0.01, seq_len=16, num_partitions=1,
                    compute_dtype=jnp.float32)
    defaults.update(kw)
    return GlmConfig(**defaults)


def rope_turns(cfg: GlmConfig):
    """A rotary pair's turn a position, ``theta^(-i / n)`` over the
    ``n = qk_rope_head_dim / 2`` pairs, float32."""
    n = cfg.qk_rope_head_dim // 2
    return jnp.asarray(
        float(cfg.rope_theta) ** (-np.arange(n, dtype=np.float64) / n),
        jnp.float32)


def attention(cfg: GlmConfig, p, h, impl=None):
    """The latent attention sub-layer on ``h [B, T, D]``."""
    dt = cfg.compute_dtype
    B, T, _ = h.shape
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    dr, R = cfg.qk_rope_head_dim, cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    turns = rope_turns(cfg)

    # the layers' names in the compiled step (obs/xprof.LAYER_SCOPES)
    with jax.named_scope("attention"):
        a = rms_norm(h, p["ln1"], eps)
        with jax.named_scope("mla_latent"):
            c_q = rms_norm(a @ p["wq_a"].astype(dt), p["q_a_norm"], eps)
            q = (c_q @ p["wq_b"].astype(dt)).reshape(B, T, H, dn + dr)
            kv = a @ p["wkv_a"].astype(dt)
            c_kv = rms_norm(kv[..., :R], p["kv_a_norm"], eps)
            k_r = rope(kv[..., R:].reshape(B, T, 1, dr), turns, 1.0)
            kv = (c_kv @ p["wkv_b"].astype(dt)).reshape(B, T, H, dn + dv)
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], turns, 1.0)],
                                axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
            v = kv[..., dn:]
        o = attend(cfg, q, k, v, None, None, impl)
        return h + o.reshape(B, T, H * dv) @ p["wo"].astype(dt)


def dense_layer(cfg: GlmConfig, p, h, impl=None):
    """A leading layer: attention, then the dense SwiGLU MLP."""
    h = attention(cfg, p, h, impl)
    return h + mlp(p, rms_norm(h, p["ln2"], cfg.rms_norm_eps),
                   cfg.compute_dtype)


def expert_layer(cfg: GlmConfig, p, bias, h, impls=(None, None),
                 forced_choice=None):
    """A layer after the dense ones on ``h [B, T, D]``: attention, then
    the experts. Returns the new ``h``, the layer's scalars and the
    router's own top-k."""
    B, T, D = h.shape
    h = attention(cfg, p, h, impls[0])
    with jax.named_scope("moe"):
        m = rms_norm(h, p["ln2"], cfg.rms_norm_eps).reshape(B * T, D)
        f, scalars, choice = expert_mix(cfg, p, bias, m, impls[1],
                                        forced_choice)
        h = h + f.astype(h.dtype).reshape(B, T, D)
    return h, scalars, choice


def mtp_input(cfg: GlmConfig, p, h, e):
    """``u = [RMSNorm_e(e) ; RMSNorm_h(h)] W_eh``: an MTP block's input
    from the next token's embedding ``e`` and the stream ``h``."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("mtp"):
        both = jnp.concatenate([rms_norm(e, p["enorm"], eps),
                                rms_norm(h, p["hnorm"], eps)], axis=-1)
        return both @ p["w_eh"].astype(cfg.compute_dtype)


def mtp_targets(batch):
    """The MTP block's inputs' ids, labels and weights ``[B, T]`` from
    the batch's ``y`` and ``w``: ``y``, ``y`` shifted left once more, and
    ``w`` shifted likewise with the last position weighing 0."""
    y = batch["y"]
    w = batch.get("w")
    w = jnp.ones(y.shape, jnp.float32) if w is None else w
    return (y, jnp.roll(y, -1, axis=1),
            jnp.roll(w, -1, axis=1).at[:, -1].set(0.0))


def init_params(cfg: GlmConfig, rng):
    V, D = cfg.padded_vocab, cfg.model_dim
    Ld, L = cfg.num_dense_layers, cfg.num_moe_layers
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    Rq, R = cfg.q_lora_rank, cfg.kv_lora_rank
    E, Eh, F = cfg.num_experts, cfg.experts_held, cfg.expert_dim
    Fd, Fs = cfg.dense_mlp_dim, cfg.num_shared_experts * cfg.expert_dim

    def attention_leaves(key, n):
        ks = jax.random.split(key, 5)
        return {
            "ln1": jnp.ones((n, D)), "ln2": jnp.ones((n, D)),
            "q_a_norm": jnp.ones((n, Rq)), "kv_a_norm": jnp.ones((n, R)),
            "wq_a": normal_init(ks[0], (n, D, Rq), D),
            "wq_b": normal_init(ks[1], (n, Rq, H * (dn + dr)), Rq),
            "wkv_a": normal_init(ks[2], (n, D, R + dr), D),
            "wkv_b": normal_init(ks[3], (n, R, H * (dn + dv)), R),
            "wo": normal_init(ks[4], (n, H * dv, D), H * dv)}

    def expert_leaves(key, n):
        ks = jax.random.split(key, 8)
        return {
            **attention_leaves(ks[0], n),
            "router": normal_init(ks[1], (n, D, E), D),
            "w_gate": normal_init(ks[2], (n, Eh, D, F), D),
            "w_up": normal_init(ks[3], (n, Eh, D, F), D),
            "w_down": normal_init(ks[4], (n, Eh, F, D), F),
            "shared_w_gate": normal_init(ks[5], (n, D, Fs), D),
            "shared_w_up": normal_init(ks[6], (n, D, Fs), D),
            "shared_w_down": normal_init(ks[7], (n, Fs, D), Fs)}

    ks = jax.random.split(rng, 8)
    params = {
        "emb": normal_init(ks[0], (V, D), D),
        "layers": expert_leaves(ks[1], L),
        "final_norm": jnp.ones((D,)),
        "head": normal_init(ks[2], (D, V), D)}
    if Ld:
        params["dense"] = {
            **attention_leaves(ks[3], Ld),
            "w_gate": normal_init(ks[4], (Ld, D, Fd), D),
            "w_up": normal_init(ks[5], (Ld, D, Fd), D),
            "w_down": normal_init(ks[6], (Ld, Fd, D), Fd)}
    if cfg.num_mtp_layers:
        k_eh, k_layer = jax.random.split(ks[7])
        params["mtp"] = {
            **jax.tree.map(lambda a: a[0], expert_leaves(k_layer, 1)),
            "enorm": jnp.ones((D,)), "hnorm": jnp.ones((D,)),
            "w_eh": normal_init(k_eh, (2 * D, D), 2 * D),
            "final_norm": jnp.ones((D,))}
    return params


def forward(cfg: GlmConfig, params, bias, batch, impls=(None, None)):
    """The model on ``batch`` under the balancing biases ``bias [L_moe +
    num_mtp_layers, E]``: ``(nll [B, T], mtp_nll [B, T] (None without
    the MTP block), the expert layers' scalars stacked over them, the
    router's own top-k [L_moe + num_mtp_layers, B * T, k])``, the MTP
    block's layer last."""
    dt = cfg.compute_dtype
    x = batch["x"]
    B, T = x.shape
    Ld, L = cfg.num_dense_layers, cfg.num_moe_layers
    h = emb_ops.embedding_lookup(params["emb"], x).astype(dt)
    forced = batch.get("expert_choice")
    if forced is not None:
        forced = forced.reshape(L + cfg.num_mtp_layers, B * T,
                                -1).astype(jnp.int32)
    keep = jax.checkpoint_policies.save_only_these_names(*KEPT)

    def scanned(h, xs):
        p, bias_l, forced_l = xs
        h, scalars, choice = expert_layer(cfg, p, bias_l, h, impls, forced_l)
        return h, (scalars, choice)

    def block(h, e, p, bias_l, forced_l):
        return expert_layer(cfg, p, bias_l, mtp_input(cfg, p, h, e), impls,
                            forced_l)

    def layer(tree, i):
        return in_compute_dtype(jax.tree.map(lambda a: a[i], tree), MATRICES,
                                dt)

    mtp_nll = None

    # what stands between the blocks' own names goes by this one: the
    # matrices' cast and the scan's own operations; inside a block its
    # layers' names win
    with jax.named_scope("layer_scan"):
        for i in range(Ld):
            h = jax.checkpoint(
                lambda h, p: dense_layer(cfg, p, h, impls[0]),
                policy=keep)(h, layer(params["dense"], i))
        h, (scalars, choice) = jax.lax.scan(
            jax.checkpoint(scanned, policy=keep), h,
            (in_compute_dtype(params["layers"], MATRICES, dt), bias[:L],
             None if forced is None else forced[:L]))
        if cfg.num_mtp_layers:
            ids, labels, _ = mtp_targets(batch)
            e = emb_ops.embedding_lookup(params["emb"], ids).astype(dt)
            p = in_compute_dtype(params["mtp"], MATRICES, dt)
            stream, s_m, c_m = jax.checkpoint(block, policy=keep)(
                h, e, p, bias[L], None if forced is None else forced[L])
            scalars = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]),
                                   scalars, s_m)
            choice = jnp.concatenate([choice, c_m[None]])

    nll = lm_head_nll(cfg, h, params["final_norm"], params["head"],
                      batch["y"])
    if cfg.num_mtp_layers:
        mtp_nll = lm_head_nll(cfg, stream, params["mtp"]["final_norm"],
                              params["head"], labels).reshape(B, T)
    return nll.reshape(B, T), mtp_nll, scalars, choice


def total_loss(cfg: GlmConfig, batch, nll, mtp_nll):
    """``(the loss, the main loss, the MTP block's loss)``: the weighted
    means of ``nll`` and of ``mtp_nll``, the latter times
    ``mtp_loss_weight`` added."""
    main = weighted_mean(nll, batch)
    if mtp_nll is None:
        return main, main, jnp.zeros((), jnp.float32)
    with jax.named_scope("lm_head"):
        w = mtp_targets(batch)[2]
        mtp = jnp.sum(mtp_nll * w) / jnp.maximum(jnp.sum(w), 1e-8)
    return main + cfg.mtp_loss_weight * mtp, main, mtp


def build_model(cfg: GlmConfig, impls=(None, None)) -> Model:
    E = cfg.num_experts
    moe_ops.check_held(E, cfg.experts_held, cfg.first_expert)
    if cfg.v_head_dim != cfg.head_dim:
        raise ValueError(
            f"the flash kernels take one head size: qk "
            f"{cfg.qk_nope_head_dim} + {cfg.qk_rope_head_dim}, v "
            f"{cfg.v_head_dim}")
    if cfg.qk_rope_head_dim % 2:
        raise ValueError("RoPE pairs the rotary dims")
    if not 0 <= cfg.num_dense_layers < cfg.num_layers:
        raise ValueError(
            f"{cfg.num_dense_layers} dense layers leave no expert layer "
            f"among {cfg.num_layers}")
    if cfg.num_shared_experts < 1:
        raise ValueError("every token takes the shared expert")
    if cfg.num_mtp_layers not in (0, 1):
        raise ValueError(f"one MTP block or none, not "
                         f"{cfg.num_mtp_layers}")

    def init_fn(rng):
        return init_params(cfg, rng), {"router_bias": jnp.zeros(
            (cfg.num_moe_layers + cfg.num_mtp_layers, E), jnp.float32)}

    def loss_fn(params, model_state, batch, rng):
        bias = model_state["router_bias"]
        nll, mtp_nll, s, _ = forward(cfg, params, bias, batch, impls)
        loss, main, mtp = total_loss(cfg, batch, nll, mtp_nll)
        new_bias = moe_ops.balance_step(bias, s["load"],
                                        cfg.load_balance_coeff)
        metrics = {
            "lm_loss": main, "mtp_nll": mtp, **moe_ops.moe_metrics(s),
            "router_gate_sum_mean": jnp.mean(s["gate_sum_mean"]),
            "router_bias_spread": jnp.mean(
                jnp.max(new_bias, axis=-1) - jnp.min(new_bias, axis=-1))}
        return loss, metrics, {"router_bias": new_bias}

    from parallax_tpu.ops.sparse_optim import SliceAdam
    table_rate = cfg.learning_rate if cfg.table_learning_rate is None \
        else cfg.table_learning_rate
    return Model(init_fn, loss_fn, optimizer=clipped_adam(cfg), stateful=True,
                 slice_updaters={"emb": SliceAdam(table_rate)},
                 gauges={**moe_ops.GAUGES, "mtp.nll": "mtp_nll",
                         "router.gate_sum_mean": "router_gate_sum_mean",
                         "router.bias_spread": "router_bias_spread"})
