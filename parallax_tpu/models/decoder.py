"""What the decoder language models share (``models/keye_vl2``,
``zaya``, ``mellum2``, ``olmo_hybrid``, ``trinity``, ``glm4_moe_lite``):
the norm, the cast of a layer stack, RoPE in half-split layout, the
causal attention and the choice of its executor, the dense SwiGLU MLP,
a sigmoid-routed expert layer's mix beside its shared expert, the
initialiser, the head and its loss, the optimiser and the synthetic
batch. Each of those
models imports these from here and nothing from another model, so a
change here is a change to every model that calls it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.ad_checkpoint import checkpoint_name

from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops import pallas_attention as pa

SLIDING, FULL = "sliding_attention", "full_attention"
# the name by which a layer's remat keeps the MLP's up and down products
MLP_KEPT = "mlp_rows"


def layer_kinds(layer_types, num_layers: int):
    """Each layer's kind, ``layer_types`` (a period of ``SLIDING`` and
    ``FULL``) repeated over ``num_layers``."""
    period = tuple(layer_types)
    if (not period or num_layers % len(period)
            or set(period) - {SLIDING, FULL}):
        raise ValueError(
            f"layer_types {period} is no period of {num_layers} "
            f"layers of {SLIDING} and {FULL}")
    return period * (num_layers // len(period))


def rms_norm(x, scale, eps):
    """RMSNorm with float32 statistics, in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def in_compute_dtype(layers, names, dtype):
    """The stacked ``layers`` with the leaves ``names`` cast to ``dtype``
    whole, before the loop over the blocks (a block's own ``.astype`` of
    them is then a no-op). The cast's transposition stands outside the
    loop with it: the backward loop writes those leaves' gradient stacks
    in ``dtype``, as the products made them, and the optimizer's fusions
    read them through the cast back to float32; with the cast inside
    the block each layer's piece was widened first and its stack
    zero-filled, written and read in float32. The same cast of the same
    float32 weight, and the same values in the gradient."""
    return {k: v.astype(dtype) if k in names else v
            for k, v in layers.items()}


def normal_init(key, shape, fan_in):
    """float32 normal weights of ``shape`` at ``1 / sqrt(fan_in)``."""
    return jax.random.normal(key, shape, jnp.float32) \
        * (1.0 / np.sqrt(fan_in))


def rope(x, w, a):
    """``x [B, T, H, 2n]`` in half-split layout, positions ``0 .. T -
    1``: pair ``i`` turned by ``t * w[i]``, ``cos`` and ``sin`` times
    ``a``. Angles in float32."""
    n = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * w  # [T, n]
    cos = (jnp.cos(angle) * a)[None, :, None, :]
    sin = (jnp.sin(angle) * a)[None, :, None, :]
    x1 = x[..., :n].astype(jnp.float32)
    x2 = x[..., n:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attend(cfg, q, k, v, window, flag, impl):
    """Causal grouped-query attention at ``cfg``'s ``head_dim`` and
    ``flash_tiles``, each query under its last ``window`` keys (None:
    every causal key) where the traced scalar ``flag`` (None: wherever
    there is a window). ``impl``: ``"flash"`` (the default on a TPU),
    ``"flash_interpret"``, or ``"xla"`` (the default elsewhere)."""
    if impl is None:
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        if flag is not None:
            window = jnp.where(flag, window, q.shape[1])
        swap = lambda a: jnp.swapaxes(a, 1, 2)      # noqa: E731
        return swap(pa._xla_attention(swap(q), swap(k), swap(v), None, True,
                                      cfg.head_dim ** -0.5, window))
    if impl not in ("flash", "flash_interpret"):
        raise ValueError(f"unknown attention impl {impl!r}")
    q_tile, block_k = cfg.flash_tiles
    return pa.flash_attention(
        q, k, v, causal=True, q_tile=int(q_tile), block_k=int(block_k),
        window=window, window_on=flag,
        interpret=impl == "flash_interpret")


def mlp(p, x, dt):
    """The dense SwiGLU MLP on ``x [B, T, D]``."""
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(x @ p["w_gate"].astype(dt))
        up = checkpoint_name(x @ p["w_up"].astype(dt), MLP_KEPT)
        return checkpoint_name((gate * up) @ p["w_down"].astype(dt),
                               MLP_KEPT)


def expert_mix(cfg, p, bias, m, impl=None, forced_choice=None):
    """``f [N, D]`` (float32) of the normalised rows ``m [N, D]`` under
    the layer's balancing biases ``bias [E]``: the shared expert plus
    the chosen experts held here (``ops/moe.sigmoid_router`` at
    ``cfg``'s ``experts_per_token``, ``route_norm`` and ``route_scale``;
    ``routed_experts`` from ``first_expert`` on), before anything the
    layer does to it; the layer's scalars; the router's own top-k.
    ``forced_choice [N, k]`` takes the place of that top-k."""
    with jax.named_scope("router"):
        route = moe_ops.sigmoid_router(
            m, p["router"], bias, cfg.experts_per_token, cfg.route_norm,
            cfg.route_scale, choice=forced_choice)
    shared = moe_ops.shared_expert(m, p["shared_w_gate"], p["shared_w_up"],
                                   p["shared_w_down"])
    moe = moe_ops.routed_experts(
        m, route.choice, route.gate, p["w_gate"], p["w_up"], p["w_down"],
        num_experts=cfg.num_experts, first_expert=cfg.first_expert,
        impl=impl)
    scalars = {"load": route.load, "gate_sum_mean": route.gate_sum_mean,
               **moe_ops.moe_scalars(moe)}
    return shared.astype(jnp.float32) + moe.out.astype(jnp.float32), \
        scalars, route.own_choice


def lm_head_nll(cfg, h, final_norm, head, y):
    """Every position's cross-entropy, flat ``[B * T]``, of the stream
    ``h [B, T, D]`` against the labels ``y [B, T]``: the final RMSNorm,
    the logits ``[B * T, V]`` in float32 through ``head [D, V]`` in the
    compute dtype, the vocabulary's padding rows masked."""
    B, T, D = h.shape
    with jax.named_scope("lm_head"):
        hidden = rms_norm(h, final_norm, cfg.rms_norm_eps)
        logits = jnp.dot(hidden.reshape(B * T, D),
                         head.astype(cfg.compute_dtype),
                         preferred_element_type=jnp.float32)
        logits = emb_ops.mask_padded_logits(logits, cfg.vocab_size)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y.reshape(B * T))


def weighted_mean(nll, batch):
    """The loss: the positions' ``nll`` (``[B, T]`` or flat) weighted by
    the batch's ``w`` (ones where it brings none), over the weights'
    sum."""
    w = batch.get("w")
    if w is None:
        w = jnp.ones(batch["x"].shape, jnp.float32)
    with jax.named_scope("lm_head"):
        w = w.reshape(nll.shape)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1e-8)


def scheduled_rate(cfg):
    """Adam's rate: ``learning_rate``, or where ``warmup_steps`` is set
    a function of the updates made so far that rises to it linearly
    from 0."""
    if not cfg.warmup_steps:
        return cfg.learning_rate
    return optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)


def clipped_adam(cfg):
    """Adam at ``scheduled_rate`` behind a clip of the global norm at
    ``max_grad_norm``: the dense group's optimiser."""
    return optax.chain(optax.clip_by_global_norm(cfg.max_grad_norm),
                       optax.adam(scheduled_rate(cfg)))


def make_batch(rng: np.random.Generator, batch_size: int, seq_len: int,
               vocab_size: int, zipf: float = 1.05):
    """Synthetic Zipf batch with ``models/lm1b``'s feed keys."""
    x = (rng.zipf(zipf, size=(batch_size, seq_len)) - 1) % vocab_size
    return {"x": x.astype(np.int32),
            "y": np.roll(x, -1, axis=1).astype(np.int32),
            "w": np.ones((batch_size, seq_len), np.float32)}
