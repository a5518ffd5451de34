"""Mellum2-12B-A2.5B — sliding-window layers three to one beside full
ones, a RoPE that differs by the layer's kind, top-8 of 64 narrow SwiGLU
experts; a chip's share of an expert-parallel layer.

Every layer is the same pre-norm block with the same weight shapes
(``config.json``: ``layer_types`` = (sliding, sliding, sliding, full) x
7, every MLP sparse), of one of two KINDS:

*Attention.* ``x = RMSNorm(h)``; ``q = x Wq`` as 32 heads of 128, ``k =
x Wk``, ``v = x Wv`` as 4 heads of 128; RMSNorm over each head on ``q``
and ``k``; RoPE over all 64 pairs of a head in half-split layout, pair
``i`` turning by ``t * w_c[i]`` with ``cos`` and ``sin`` multiplied by
``a_c``, ``c`` the layer's kind. Sliding: ``w[i] = theta^(-i / 64)``,
``a = 1``. Full (YaRN): the pairs slower than ``beta_slow`` turns in the
original context interpolated by ``factor``, those faster than
``beta_fast`` left, a linear ramp between (``rope_tables``), ``a = 0.1
ln(factor) + 1`` on both of ``q`` and ``k``. Query ``t`` of head ``h``
reads key/value head ``h // 8`` over the keys ``s`` with ``0 <= t - s <
W_c``: ``sliding_window`` on a sliding layer (the token itself and the
``W - 1`` before it), every causal key on a full one
(``ops/pallas_attention.flash_attention`` with its ``window``); ``h += o
Wo``.

*Experts.* ``y = RMSNorm(h)``; ``p = softmax(y Wr)`` over all 64 in
float32, the 8 largest, their gates renormalised to one
(``ops/moe.linear_router``); ``h += sum_e g_e (silu(y Wg_e) * (y Wu_e))
Wd_e`` over the chosen experts held here (``ops/moe.routed_experts``:
``experts_held`` from ``first_expert`` on, dropless), nothing for the
others. No shared expert (``models/trinity`` has one:
``ops/moe.shared_expert``).

**Two kinds under ONE loop body.** The layers run under one ``lax.scan``
over their stacked parameters; beside the weights its ``xs`` carry
per-layer constants built from the config, no parameters: the RoPE's
``w [L, 64]`` and ``a [L]``, and whether the layer is a window layer.
The body is traced once; ``flash_attention`` is handed the window and
that flag, and chooses with a ``cond`` between the windowed kernels
(``flash_*_win`` under the scope ``window_attention``) and the plain
ones, forward and backward. L bodies of straight-line code, or a period
of four in the loop, cost what ``PERF.md`` section 6 (PR 32) measured.

Untied head; the embedding is a gather-only table on the engine's slices
path (``SliceAdam``), everything else Adam behind a global-norm clip;
bfloat16 compute on float32 weights, the router, every softmax, every
norm's statistics and RoPE's angles in float32; each layer
rematerialised, keeping the attention's output and logsumexp and the
experts' row buffers so that no kernel runs a second time; the layers'
matrices cast to bfloat16 before the loop
(``models/decoder.in_compute_dtype``). The loss is the cross-entropy
plus ``router_aux_loss_coef`` x the load-balance loss.

The chip's share (``PERF.md`` section 4): each layer's 64 experts are
shared by four chips, the vocabulary's rows by eight; what the absent
experts would add is left out, and that partial result is what the next
layer reads. Not built: the multi-token-prediction head the model's
description names (``config`` has no key for it).

Batch contract as ``models/lm1b``: ``x``, ``y`` int32 ``[B, T]``, ``w``
float weights; a batch may bring ``expert_choice`` int32 ``[L, B, T,
k]``, which then takes the place of the router's own top-k (a
comparison under one routing; not on the training path).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.engine import Model
from parallax_tpu.models.decoder import (  # noqa: F401
    FULL, SLIDING, attend, clipped_adam, in_compute_dtype, layer_kinds,
    lm_head_nll, make_batch, normal_init, rms_norm, rope, scheduled_rate,
    weighted_mean)
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops import pallas_attention as pa


@dataclasses.dataclass
class Mellum2Config:
    vocab_size: int = 98304
    model_dim: int = 2304
    num_layers: int = 28
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    # one name a layer, or a period of names repeated over the layers
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 1024
    rope_theta: float = 5e5
    # rope_parameters.full_attention (YaRN); the sliding layers' RoPE is
    # the default one at the same theta
    yarn_factor: float = 16.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    # None: 0.1 ln(factor) + 1, which is what the config publishes
    yarn_attention_factor: Optional[float] = None
    rms_norm_eps: float = 1e-6
    # the experts: the router is num_experts wide whatever is held here
    num_experts: int = 64
    experts_per_token: int = 8
    expert_dim: int = 896
    experts_held: int = 64
    first_expert: int = 0
    seq_len: int = 8192
    router_aux_loss_coef: float = 0.001
    learning_rate: float = 3e-4
    # steps over which the learning rate rises linearly from 0
    warmup_steps: int = 0
    max_grad_norm: float = 1.0
    # the dense flash kernels' tiles (queries, keys), both kinds of
    # layer's: ZAYA's, and the fastest of nine pairs under the window
    # too (PERF.md section 6, PR 33)
    flash_tiles: tuple = (512, 512)
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``layer_types`` repeated over the depth."""
        return layer_kinds(self.layer_types, self.num_layers)


# the layers' leaves that a block multiplies in the compute dtype
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def tiny_config(**kw) -> Mellum2Config:
    """Two periods of (sliding, sliding, full) at toy widths; the YaRN
    ramp lies inside the head's 8 pairs."""
    defaults = dict(vocab_size=96, model_dim=32, num_layers=6, num_heads=4,
                    num_kv_heads=2, head_dim=16,
                    layer_types=(SLIDING, SLIDING, FULL), sliding_window=5,
                    rope_theta=100.0, yarn_factor=4.0,
                    yarn_original_max_position=16, yarn_beta_fast=2.0,
                    yarn_beta_slow=0.25, num_experts=8, experts_per_token=2,
                    expert_dim=16, experts_held=4, first_expert=0,
                    seq_len=16, num_partitions=1,
                    compute_dtype=jnp.float32)
    defaults.update(kw)
    return Mellum2Config(**defaults)


def yarn_ramp(cfg: Mellum2Config):
    """``(low, high, ramp [head_dim / 2])`` of YaRN's frequency blend, as
    ``transformers``' ``_compute_yarn_parameters``: ``d(r)`` is the pair
    that makes ``r`` turns over the original context; pairs below
    ``low = floor(d(beta_fast))`` keep their frequency, pairs above
    ``high = ceil(d(beta_slow))`` are interpolated, a linear ramp
    between."""
    dim, n = cfg.head_dim, cfg.head_dim // 2

    def d(turns):
        return dim * math.log(cfg.yarn_original_max_position
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(d(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(d(cfg.yarn_beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = np.clip((np.arange(n, dtype=np.float64) - low) / span, 0.0, 1.0)
    return low, high, ramp


def rope_tables(cfg: Mellum2Config):
    """The scan's per-layer constants: ``w [L, head_dim / 2]`` (a pair's
    turn a position), ``a [L]`` (what ``cos`` and ``sin`` are multiplied
    by), float32, and ``is_window [L]``. From the config alone."""
    n = cfg.head_dim // 2
    plain = float(cfg.rope_theta) ** (-np.arange(n, dtype=np.float64) / n)
    ramp = yarn_ramp(cfg)[2]
    yarn = plain * ((1.0 - ramp) + ramp / cfg.yarn_factor)
    a_full = cfg.yarn_attention_factor
    if a_full is None:
        a_full = 0.1 * math.log(cfg.yarn_factor) + 1.0
    window = np.array([kind == SLIDING for kind in cfg.kinds])
    return {"rope_w": jnp.asarray(np.where(window[:, None], plain, yarn),
                                  jnp.float32),
            "rope_a": jnp.asarray(np.where(window, 1.0, a_full),
                                  jnp.float32),
            "is_window": jnp.asarray(window)}


def _attend(cfg: Mellum2Config, q, k, v, is_window, impl):
    """Causal grouped-query attention, under the window where
    ``is_window`` (a traced scalar of the scan)."""
    kinds = set(cfg.kinds)
    return attend(cfg, q, k, v,
                  cfg.sliding_window if SLIDING in kinds else None,
                  is_window if len(kinds) == 2 else None, impl)


def _layer(cfg: Mellum2Config, p, kind, h, impls=(None, None),
           forced_choice=None):
    """One block on ``h [B, T, D]``; ``kind`` holds the layer's
    ``rope_w``, ``rope_a`` and ``is_window``. Returns the new ``h``, the
    layer's scalars and the router's own top-k. ``forced_choice [B * T,
    k]`` takes the place of that top-k."""
    dt = cfg.compute_dtype
    B, T, D = h.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps

    # the layers' names in the compiled step (obs/xprof.LAYER_SCOPES);
    # a window layer's kernels go by the inner `window_attention`
    with jax.named_scope("attention"):
        x = rms_norm(h, p["ln1"], eps)
        q = rms_norm((x @ p["wq"].astype(dt)).reshape(B, T, Hq, Dh),
                     p["q_norm"], eps)
        k = rms_norm((x @ p["wk"].astype(dt)).reshape(B, T, Hkv, Dh),
                     p["k_norm"], eps)
        v = (x @ p["wv"].astype(dt)).reshape(B, T, Hkv, Dh)
        q = rope(q, kind["rope_w"], kind["rope_a"])
        k = rope(k, kind["rope_w"], kind["rope_a"])
        o = _attend(cfg, q, k, v, kind["is_window"], impls[0])
        h = h + o.reshape(B, T, Hq * Dh) @ p["wo"].astype(dt)

    with jax.named_scope("moe"):
        y = rms_norm(h, p["ln2"], eps).reshape(B * T, D)
        own = moe_ops.linear_router(y, p["router"], cfg.experts_per_token)
        route = own if forced_choice is None else moe_ops.linear_router(
            y, p["router"], cfg.experts_per_token, choice=forced_choice)
        moe = moe_ops.routed_experts(
            y, route.choice, route.gate, p["w_gate"], p["w_up"],
            p["w_down"], num_experts=cfg.num_experts,
            first_expert=cfg.first_expert, impl=impls[1])
        h = h + moe.out.reshape(B, T, D)
    scalars = {"aux_loss": route.aux_loss, **moe_ops.moe_scalars(moe)}
    return h, scalars, own.choice


def init_params(cfg: Mellum2Config, rng):
    V, D, L = cfg.padded_vocab, cfg.model_dim, cfg.num_layers
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E, Eh, F = cfg.num_experts, cfg.experts_held, cfg.expert_dim
    ks = jax.random.split(rng, 10)
    layers = {
        "ln1": jnp.ones((L, D)), "ln2": jnp.ones((L, D)),
        "q_norm": jnp.ones((L, Dh)), "k_norm": jnp.ones((L, Dh)),
        "wq": normal_init(ks[0], (L, D, Hq * Dh), D),
        "wk": normal_init(ks[1], (L, D, Hkv * Dh), D),
        "wv": normal_init(ks[2], (L, D, Hkv * Dh), D),
        "wo": normal_init(ks[3], (L, Hq * Dh, D), Hq * Dh),
        "router": normal_init(ks[4], (L, D, E), D),
        "w_gate": normal_init(ks[5], (L, Eh, D, F), D),
        "w_up": normal_init(ks[6], (L, Eh, D, F), D),
        "w_down": normal_init(ks[7], (L, Eh, F, D), F),
    }
    # the embedding at unit scale, as Keye's: a token's own row and not
    # the attention's near-uniform mean decides where it is routed
    return {"emb": jax.random.normal(ks[8], (V, D)), "layers": layers,
            "final_norm": jnp.ones((D,)),
            "head": normal_init(ks[9], (D, V), D)}


def forward(cfg: Mellum2Config, params, batch, impls=(None, None)):
    """The model on ``batch``: ``(nll [B, T], the layers' scalars
    stacked over them, the router's own top-k [L, B * T, k])``."""
    dt = cfg.compute_dtype
    x = batch["x"]
    B, T = x.shape
    L = cfg.num_layers
    h = emb_ops.embedding_lookup(params["emb"], x).astype(dt)
    forced = batch.get("expert_choice")
    if forced is not None:
        forced = forced.reshape(L, B * T, -1).astype(jnp.int32)

    def scanned(h, xs):
        p, kind, forced_l = xs
        h, scalars, choice = _layer(cfg, p, kind, h, impls, forced_l)
        return h, (scalars, choice)

    # what a rematerialised layer keeps for its backward pass: the
    # attention's output and logsumexp (named after the `cond` between
    # the two kinds' kernels) and the experts' row buffers, so that no
    # kernel runs a second time
    scanned = jax.checkpoint(
        scanned, policy=jax.checkpoint_policies.save_only_these_names(
            pa.KEPT, moe_ops.KEPT))
    # the scan's own operations (the matrices' cast, a layer's weights
    # and constants cut out of their stacks, its kept arrays and
    # gradients written into theirs, the loop) go by this name; inside a
    # block its layers' names win
    with jax.named_scope("layer_scan"):
        layers = in_compute_dtype(params["layers"], MATRICES, dt)
        h, (scalars, choice) = jax.lax.scan(
            scanned, h, (layers, rope_tables(cfg), forced))

    nll = lm_head_nll(cfg, h, params["final_norm"], params["head"],
                      batch["y"])
    return nll.reshape(B, T), scalars, choice


def build_model(cfg: Mellum2Config, impls=(None, None)) -> Model:
    moe_ops.check_held(cfg.num_experts, cfg.experts_held, cfg.first_expert)
    if cfg.num_heads % cfg.num_kv_heads or cfg.head_dim % 2:
        raise ValueError("the query heads group onto the key/value heads, "
                         "and RoPE pairs a head's entries")
    cfg.kinds       # a layer_types that is no period is refused here

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng):
        nll, s, _ = forward(cfg, params, batch, impls)
        lm_loss = weighted_mean(nll, batch)
        aux_loss = jnp.mean(s["aux_loss"])
        loss = lm_loss + cfg.router_aux_loss_coef * aux_loss
        return loss, {"lm_loss": lm_loss, "aux_loss": aux_loss,
                      **moe_ops.moe_metrics(s)}

    from parallax_tpu.ops.sparse_optim import SliceAdam
    return Model(init_fn, loss_fn, optimizer=clipped_adam(cfg),
                 slice_updaters={"emb": SliceAdam(cfg.learning_rate)},
                 gauges=moe_ops.GAUGES)
