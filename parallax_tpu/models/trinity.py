"""Trinity-Mini (arcee-ai, ``model_type`` ``afmoe``) — a gated
grouped-query attention with RoPE on its window layers only, a leading
dense layer before the expert layers, sigmoid top-8 of 128 narrow SwiGLU
experts chosen under balancing biases beside a shared expert, a norm on
every sub-layer's output; a chip's share of an expert-parallel layer.

Written as the published modelling code (``transformers``'
``modeling_afmoe.py``) computes it. ``h_0 = sqrt(D) * emb[ids]``
(``mup_enabled``). Every layer is two sub-layers, each normed on its
input AND on its output (four RMSNorms a layer):

*Attention, every layer.* ``a = RMSNorm(h)``; ``q = a Wq`` as 32 heads
of 128, ``k = a Wk``, ``v = a Wv`` as 4 heads, ``g = a Wg`` (the gate,
as wide as ``q``, no bias); RMSNorm over each head on ``q`` and ``k``.
**On a sliding layer** RoPE over all 64 pairs of a head in half-split
layout (``models/decoder.rope``) and the keys ``0 <= t - s <
sliding_window``; **on a full layer no RoPE at all** and every causal
key. ``o = softmax(q k^T / sqrt(128)) v``
(``ops/pallas_attention.flash_attention`` with its ``window``); ``o <- o
* sigmoid(g)`` (the scope ``attn_gate``); ``h += RMSNorm(o Wo)``.

*Dense layers* (the first ``num_dense_layers``): ``m = RMSNorm(h)``; ``h
+= RMSNorm(Wd (silu(Wg m) * Wu m))`` at ``dense_mlp_dim``
(``models/decoder.mlp``, the scope ``mlp``).

*Expert layers.* ``m = RMSNorm(h)``; ``s = sigmoid(m Wr)`` over all 128
in float32, the choice ``top8(s + b)``, the gates ``s`` of the chosen
WITHOUT ``b``, over their sum and times ``route_scale``
(``ops/moe.sigmoid_router``); ``f = shared(m) + sum_c w_c expert_c(m)``
over the chosen experts held here (``ops/moe.routed_experts``:
``experts_held`` from ``first_expert`` on, dropless) beside the shared
expert every token takes (``ops/moe.shared_expert``: one SwiGLU of
``num_shared_experts * expert_dim``); ``h += RMSNorm(f)``. The balancing
biases ``b [L_moe, E]`` are no parameters: they are the ``model_state``
of a stateful ``Model``, no gradient reaches them, and each step moves
them by ``load_balance_coeff`` against the sign of each expert's load
over its layer's mean (``ops/moe.balance_step``), from the loads over
ALL ``E`` experts of this chip's tokens. No auxiliary loss.

**Two layer bodies with different parameter trees in one model.** The
dense layers (``params["dense"]``, stacked) run before the loop as
straight-line code; the expert layers (``params["layers"]``) under ONE
``lax.scan`` whose ``xs`` carry, beside the weights, per-layer constants
built from the config and the layer's biases: ``rope_w [L, 64]`` (a
pair's turn a position; ZEROS on a full layer, which turn nothing) and
``is_window``, which ``flash_attention`` takes as the flag of its
``cond`` between the windowed and the plain kernels (Mellum2's).
``num_layers`` counts the dense layers held plus the expert layers;
``layer_types`` gives every held layer's kind, the dense ones' first.

Final RMSNorm, untied head; the embedding is a gather-only table on the
engine's slices path (``SliceAdam`` at the constant
``table_learning_rate``), everything else Adam behind a global-norm clip;
bfloat16 compute on float32 weights, the router, every softmax, every
norm's statistics, RoPE's angles and the gate's sigmoid in float32; each
layer rematerialised, keeping the attention's output and logsumexp and
the experts' row buffers so that no kernel runs a second time
(``KEPT``); the layers' matrices cast to bfloat16 before the loop
(``models/decoder.in_compute_dtype``).

The chip's share (``PERF.md`` section 4): each layer's 128 experts are
shared by eight chips, the vocabulary's rows by eight; what the absent
experts would add is left out, and that partial result is what the next
layer reads. The shared expert is computed by every chip alike: where
the chips' shares are added it counts once (``tests/test_trinity.py``).

Batch contract as ``models/lm1b``: ``x``, ``y`` int32 ``[B, T]``, ``w``
float weights; a batch may bring ``expert_choice`` int32 ``[L_moe, B, T,
k]``, which then takes the place of the router's own top-k (a comparison
under one routing; not on the training path).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.engine import Model
from parallax_tpu.models.decoder import (  # noqa: F401
    FULL, SLIDING, attend, clipped_adam, expert_mix, in_compute_dtype,
    layer_kinds, lm_head_nll, make_batch, mlp, normal_init, rms_norm, rope,
    weighted_mean)
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops import pallas_attention as pa

# what a rematerialised layer keeps for its backward pass: the
# attention's output and logsumexp and the experts' row buffers (the ops
# name them), so that no kernel runs a second time. Nothing else: with
# the attention's four projections, the dense MLP's and the shared
# expert's products kept too the step read 29,558 tokens/s/chip against
# 29,464 (+0.3 %) for 1.1 GB more by the compiler's count, 15.57 GB
# against 14.47 (PERF.md section 6, PR 39)
KEPT = (pa.KEPT, moe_ops.KEPT)


@dataclasses.dataclass
class TrinityConfig:
    vocab_size: int = 200192
    model_dim: int = 2048
    # the dense layers held plus the expert layers
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    # one name a layer, or a period of names repeated over the layers;
    # the dense layers' kinds come first
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 2048
    # the sliding layers' RoPE; a full layer has none
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    # the leading layers whose MLP is dense, and its width
    num_dense_layers: int = 2
    dense_mlp_dim: int = 6144
    # the experts: the router is num_experts wide whatever is held here
    num_experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 1024
    experts_held: int = 128
    first_expert: int = 0
    # experts every token takes, as one SwiGLU of their summed width
    num_shared_experts: int = 1
    # the gates: the chosen scores over their sum (route_norm), times
    # route_scale
    route_norm: bool = True
    route_scale: float = 2.826
    # how far a step moves each balancing bias
    load_balance_coeff: float = 1e-3
    seq_len: int = 8192
    learning_rate: float = 3e-4
    # steps over which the learning rate rises linearly from 0
    warmup_steps: int = 0
    # lazy Adam's constant rate on the table (None: `learning_rate`).
    # The rows lie at 1 / sqrt(D) and reach the stream times sqrt(D), so
    # at one rate the stream's first rows move sqrt(D) times as fast as
    # any other weight moves what it feeds
    table_learning_rate: Optional[float] = None
    max_grad_norm: float = 1.0
    # the dense flash kernels' tiles (queries, keys), both kinds of
    # layer's
    flash_tiles: tuple = (512, 512)
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each held layer's kind, ``layer_types`` repeated over the
        depth: the dense layers' first, then the expert layers'."""
        return layer_kinds(self.layer_types, self.num_layers)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers


# the leaves that a block multiplies in the compute dtype
ATTENTION_MATRICES = ("wq", "wk", "wv", "w_attn_gate", "wo")
MATRICES = ATTENTION_MATRICES + ("w_gate", "w_up", "w_down", "shared_w_gate",
                                 "shared_w_up", "shared_w_down")


def tiny_config(**kw) -> TrinityConfig:
    """One dense layer, then two periods of (sliding, sliding, full) at
    toy widths."""
    defaults = dict(vocab_size=96, model_dim=32, num_layers=7, num_heads=4,
                    num_kv_heads=2, head_dim=16,
                    layer_types=(SLIDING,) + (SLIDING, SLIDING, FULL) * 2,
                    sliding_window=5, rope_theta=100.0, num_dense_layers=1,
                    dense_mlp_dim=48, num_experts=8, experts_per_token=2,
                    expert_dim=16, experts_held=4, first_expert=0,
                    route_scale=1.5, load_balance_coeff=0.01, seq_len=16,
                    num_partitions=1, compute_dtype=jnp.float32)
    defaults.update(kw)
    return TrinityConfig(**defaults)


def rope_tables(cfg: TrinityConfig):
    """Each held layer's constants, from the config alone: ``rope_w
    [num_layers, head_dim / 2]`` float32 (a pair's turn a position:
    ``theta^(-i / n)`` on a sliding layer, ZEROS on a full one, which
    turn nothing) and ``is_window [num_layers]``."""
    n = cfg.head_dim // 2
    plain = float(cfg.rope_theta) ** (-np.arange(n, dtype=np.float64) / n)
    window = np.array([kind == SLIDING for kind in cfg.kinds])
    return {"rope_w": jnp.asarray(np.where(window[:, None], plain, 0.0),
                                  jnp.float32),
            "is_window": jnp.asarray(window)}


def _attend(cfg: TrinityConfig, q, k, v, is_window, impl):
    """Causal grouped-query attention (``models/decoder.attend``), under
    the window where ``is_window``: a bool (a layer whose kind the trace
    knows) or a traced scalar of the scan."""
    traced = not isinstance(is_window, (bool, np.bool_))
    return attend(cfg, q, k, v,
                  cfg.sliding_window if traced or is_window else None,
                  is_window if traced else None, impl)


def attention(cfg: TrinityConfig, p, kind, h, impl=None):
    """The attention sub-layer on ``h [B, T, D]``; ``kind`` holds the
    layer's ``rope_w`` and ``is_window``."""
    dt = cfg.compute_dtype
    B, T, _ = h.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps

    # the layers' names in the compiled step (obs/xprof.LAYER_SCOPES);
    # a window layer's kernels go by the inner `window_attention`, the
    # gate by `attn_gate`
    with jax.named_scope("attention"):
        a = rms_norm(h, p["ln1"], eps)
        q = rms_norm((a @ p["wq"].astype(dt)).reshape(B, T, Hq, Dh),
                     p["q_norm"], eps)
        k = rms_norm((a @ p["wk"].astype(dt)).reshape(B, T, Hkv, Dh),
                     p["k_norm"], eps)
        v = (a @ p["wv"].astype(dt)).reshape(B, T, Hkv, Dh)
        q = rope(q, kind["rope_w"], 1.0)
        k = rope(k, kind["rope_w"], 1.0)
        o = _attend(cfg, q, k, v, kind["is_window"], impl)
        with jax.named_scope("attn_gate"):
            g = a @ p["w_attn_gate"].astype(dt)
            o = (o.reshape(B, T, Hq * Dh).astype(jnp.float32)
                 * jax.nn.sigmoid(g.astype(jnp.float32))).astype(dt)
        return h + rms_norm(o @ p["wo"].astype(dt), p["ln1_post"], eps)


def dense_layer(cfg: TrinityConfig, p, kind, h, impl=None):
    """A leading layer: attention, then the dense SwiGLU MLP."""
    h = attention(cfg, p, kind, h, impl)
    m = rms_norm(h, p["ln2"], cfg.rms_norm_eps)
    return h + rms_norm(mlp(p, m, cfg.compute_dtype), p["ln2_post"],
                        cfg.rms_norm_eps)


def expert_layer(cfg: TrinityConfig, p, kind, bias, h, impls=(None, None),
                 forced_choice=None):
    """A layer after the dense ones on ``h [B, T, D]``: attention, then
    the experts. Returns the new ``h``, the layer's scalars and the
    router's own top-k."""
    B, T, D = h.shape
    eps = cfg.rms_norm_eps
    h = attention(cfg, p, kind, h, impls[0])
    with jax.named_scope("moe"):
        m = rms_norm(h, p["ln2"], eps).reshape(B * T, D)
        f, scalars, choice = expert_mix(cfg, p, bias, m, impls[1],
                                        forced_choice)
        h = h + rms_norm(f, p["ln2_post"], eps).astype(h.dtype) \
            .reshape(B, T, D)
    return h, scalars, choice


def init_params(cfg: TrinityConfig, rng):
    V, D = cfg.padded_vocab, cfg.model_dim
    Ld, L = cfg.num_dense_layers, cfg.num_moe_layers
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E, Eh, F = cfg.num_experts, cfg.experts_held, cfg.expert_dim
    Fd, Fs = cfg.dense_mlp_dim, cfg.num_shared_experts * cfg.expert_dim

    def attention_leaves(key, n):
        ks = jax.random.split(key, 5)
        return {
            "ln1": jnp.ones((n, D)), "ln1_post": jnp.ones((n, D)),
            "ln2": jnp.ones((n, D)), "ln2_post": jnp.ones((n, D)),
            "q_norm": jnp.ones((n, Dh)), "k_norm": jnp.ones((n, Dh)),
            "wq": normal_init(ks[0], (n, D, Hq * Dh), D),
            "wk": normal_init(ks[1], (n, D, Hkv * Dh), D),
            "wv": normal_init(ks[2], (n, D, Hkv * Dh), D),
            "w_attn_gate": normal_init(ks[3], (n, D, Hq * Dh), D),
            "wo": normal_init(ks[4], (n, Hq * Dh, D), Hq * Dh)}

    ks = jax.random.split(rng, 13)
    params = {
        # rows at 1 / sqrt(D): the stream starts at unit scale behind
        # the sqrt(D) multiplier, so that a token's own row and not the
        # attention's near-uniform mean decides where it is routed
        "emb": normal_init(ks[0], (V, D), D),
        "layers": {
            **attention_leaves(ks[1], L),
            "router": normal_init(ks[2], (L, D, E), D),
            "w_gate": normal_init(ks[3], (L, Eh, D, F), D),
            "w_up": normal_init(ks[4], (L, Eh, D, F), D),
            "w_down": normal_init(ks[5], (L, Eh, F, D), F),
            "shared_w_gate": normal_init(ks[6], (L, D, Fs), D),
            "shared_w_up": normal_init(ks[7], (L, D, Fs), D),
            "shared_w_down": normal_init(ks[8], (L, Fs, D), Fs)},
        "final_norm": jnp.ones((D,)),
        "head": normal_init(ks[9], (D, V), D)}
    if Ld:
        params["dense"] = {
            **attention_leaves(ks[10], Ld),
            "w_gate": normal_init(ks[11], (Ld, D, Fd), D),
            "w_up": normal_init(jax.random.fold_in(ks[11], 1), (Ld, D, Fd), D),
            "w_down": normal_init(ks[12], (Ld, Fd, D), Fd)}
    return params


def forward(cfg: TrinityConfig, params, bias, batch, impls=(None, None)):
    """The model on ``batch`` under the balancing biases ``bias [L_moe,
    E]``: ``(nll [B, T], the expert layers' scalars stacked over them,
    the router's own top-k [L_moe, B * T, k])``."""
    dt = cfg.compute_dtype
    x = batch["x"]
    B, T = x.shape
    D, Ld, L = cfg.model_dim, cfg.num_dense_layers, cfg.num_moe_layers
    h = (emb_ops.embedding_lookup(params["emb"], x) * np.sqrt(D)).astype(dt)
    forced = batch.get("expert_choice")
    if forced is not None:
        forced = forced.reshape(L, B * T, -1).astype(jnp.int32)
    kinds = cfg.kinds
    tables = rope_tables(cfg)
    keep = jax.checkpoint_policies.save_only_these_names(*KEPT)

    def scanned(h, xs):
        # the layer's kind is the loop's data
        p, kind, bias_l, forced_l = xs
        h, scalars, choice = expert_layer(cfg, p, kind, bias_l, h, impls,
                                          forced_l)
        return h, (scalars, choice)

    scanned = jax.checkpoint(scanned, policy=keep)
    # what stands between the blocks' own names goes by this one: the
    # matrices' cast, a dense layer's second pair of norms, and the
    # scan's own operations (a layer's weights and constants cut out of
    # their stacks, its kept arrays and gradients written into theirs,
    # the loop); inside a block its layers' names win
    with jax.named_scope("layer_scan"):
        # the leading dense layers, straight-line: another parameter
        # tree than the loop's, and their kind is the trace's to know
        for i in range(Ld):
            kind = {"rope_w": tables["rope_w"][i],
                    "is_window": kinds[i] == SLIDING}
            p = in_compute_dtype(
                jax.tree.map(lambda a: a[i], params["dense"]), MATRICES, dt)
            h = jax.checkpoint(
                lambda h, p, kind=kind: dense_layer(cfg, p, kind, h,
                                                    impls[0]),
                policy=keep)(h, p)
        layers = in_compute_dtype(params["layers"], MATRICES, dt)
        h, (scalars, choice) = jax.lax.scan(
            scanned, h,
            (layers, jax.tree.map(lambda a: a[Ld:], tables), bias, forced))

    nll = lm_head_nll(cfg, h, params["final_norm"], params["head"],
                      batch["y"])
    return nll.reshape(B, T), scalars, choice


def build_model(cfg: TrinityConfig, impls=(None, None)) -> Model:
    E = cfg.num_experts
    moe_ops.check_held(E, cfg.experts_held, cfg.first_expert)
    if cfg.num_heads % cfg.num_kv_heads or cfg.head_dim % 2:
        raise ValueError("the query heads group onto the key/value heads, "
                         "and RoPE pairs a head's entries")
    if not 0 <= cfg.num_dense_layers < cfg.num_layers:
        raise ValueError(
            f"{cfg.num_dense_layers} dense layers leave no expert layer "
            f"among {cfg.num_layers}")
    if cfg.num_shared_experts < 1:
        raise ValueError("every token takes the shared expert")
    cfg.kinds       # a layer_types that is no period is refused here

    def init_fn(rng):
        return init_params(cfg, rng), \
            {"router_bias": jnp.zeros((cfg.num_moe_layers, E), jnp.float32)}

    def loss_fn(params, model_state, batch, rng):
        bias = model_state["router_bias"]
        nll, s, _ = forward(cfg, params, bias, batch, impls)
        loss = weighted_mean(nll, batch)
        new_bias = moe_ops.balance_step(bias, s["load"],
                                        cfg.load_balance_coeff)
        metrics = {
            "lm_loss": loss, **moe_ops.moe_metrics(s),
            "router_gate_sum_mean": jnp.mean(s["gate_sum_mean"]),
            "router_bias_spread": jnp.mean(
                jnp.max(new_bias, axis=-1) - jnp.min(new_bias, axis=-1))}
        return loss, metrics, {"router_bias": new_bias}

    from parallax_tpu.ops.sparse_optim import SliceAdam
    table_rate = cfg.learning_rate if cfg.table_learning_rate is None \
        else cfg.table_learning_rate
    return Model(init_fn, loss_fn, optimizer=clipped_adam(cfg), stateful=True,
                 slice_updaters={"emb": SliceAdam(table_rate)},
                 gauges={**moe_ops.GAUGES,
                         "router.gate_sum_mean": "router_gate_sum_mean",
                         "router.bias_spread": "router_bias_spread"})
