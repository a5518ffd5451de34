"""ZAYA1-8B — attention in a compressed latent behind convolutions
(CCA), a top-1 MLP router that carries its state from layer to layer and
is balanced by biases, wide SwiGLU experts, a tied table; a chip's share
of an expert-parallel layer.

Every layer is the same pre-norm block (``config.json``: 40 layers of
type ``hybrid``), two sub-layers on the residual stream ``x``:

*Attention (CCA).* ``u = RMSNorm(x)``; a query latent ``q~ = u Wq`` of
``Hq`` heads x ``d`` (2048 -> 1024) and a key latent ``k~ = u Wk`` of
``Hkv`` heads (2048 -> 256); the values' first ``Hkv / 2`` heads from
this token and the others from the previous one (``v_t = [u_t Wv1 ;
u_{t-1} Wv2]``, ``u_{-1} = 0``). ``z = [q~ ; k~]`` goes through two
causal convolutions along the sequence: depthwise with ``cca_time0``
taps (a weight a channel and tap), then grouped with ``cca_time1`` taps,
one group a head (``A [Hq + Hkv, taps, d, d]``, ``z'' = sum_i z'_{t-i}
A[h, i]``); tap ``i`` reads position ``t - i``, zeros before the
sequence: a shift and a multiply-add, no convolution primitive. Then the
q-k mean (``q[h] = z''[h] + (q~[h] + k~[h // g]) / 2``; ``k[j] =
z''[Hq + j] + (mean of its group's q~ + k~[j]) / 2``), each head
normalised to length ``sqrt(d)`` (``k`` times a learned temperature a
key/value head), RoPE on the first ``partial_rotary_factor`` of each
head, and causal grouped-query attention in that latent
(``ops/pallas_attention.flash_attention``: ``Hq`` query heads on
``Hkv`` key/value heads, no K or V repeated); ``x += o Wo``.

*Experts.* ``u = RMSNorm(x)``; the router's state ``r_l = u Wd + b +
gamma_l * r_{l-1}`` (``router_hidden_size`` wide, ``r_{-1} = 0``) is
what layer ``l + 1`` receives: the ``lax.scan`` over the blocks carries
``(x, r)``. ``p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(r_l) + b1) +
b2))`` over all ``E`` experts in float32; the choice is ``argmax(p +
beta_l)`` and the gate ``p`` of the chosen expert as it is (renormalised
a top-1 gate is 1 and the router learns nothing). ``x += gate * expert
(u)`` for the experts held here (``ops/moe.routed_experts`` under this
routing: ``experts_held`` from ``first_expert`` on, dropless), nothing
for the others. The balancing biases ``beta [L, E]`` are no parameters:
they are the ``model_state`` of a stateful ``Model``, no gradient
reaches them, and each step moves them by ``bias_update_rate`` against
the sign of each expert's load over the mean (the auxiliary-loss-free
rule). No auxiliary loss.

*Ends.* One table ``emb [V, D]``: ``x_0 = sqrt(D) * emb[ids]`` and,
after the final RMSNorm, logits ``h emb^T``. The table is therefore
gathered AND multiplied, which ``core/classify`` calls DENSE ("gathered
but also used densely": the paper's rule, a variable is sparse only if
every use is a gather): it rides the dense optimizer, and its gradient
is the sum of a scatter-add and a ``[V, D]`` product. Nothing here
names a slice updater.

The chip's share (``PERF.md`` section 4): each layer's 16 experts are
shared by two chips, the table's rows by eight; what the absent experts
would add is left out, and that partial result is what the next layer
reads.

Training: Adam behind a global-norm clip on every parameter; bfloat16
compute on float32 weights, with the router (projection, carry, MLP,
softmax, gate), every norm's statistics, RoPE's angles and every softmax
in float32; each layer rematerialised, keeping the attention's output
and logsumexp and the experts' row buffers so that no kernel runs a
second time; the layers' matrices are cast to bfloat16 before the
``lax.scan`` (``models/decoder.in_compute_dtype``), so that their
gradient stacks leave the backward loop in bfloat16.

Not built (departures, ``benchmark/configs/zaya1-8b.json``): a router
output that skips the layer, and learned scales on the residual stream.

Batch contract as ``models/lm1b``: ``x``, ``y`` int32 ``[B, T]``, ``w``
float weights; a batch may bring ``expert_choice`` int32 ``[L, B, T]``,
which then takes the place of the router's own choice (a comparison
under one routing; not on the training path).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.engine import Model
from parallax_tpu.models.decoder import (  # noqa: F401
    attend, clipped_adam, in_compute_dtype, lm_head_nll, make_batch,
    normal_init, rms_norm, scheduled_rate, weighted_mean)
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import moe as moe_ops
from parallax_tpu.ops import pallas_attention as pa


@dataclasses.dataclass
class ZayaConfig:
    vocab_size: int = 262272
    model_dim: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    rms_norm_eps: float = 1e-5
    # the experts: the router is num_experts wide whatever is held here
    num_experts: int = 16
    experts_per_token: int = 1
    expert_dim: int = 2048
    experts_held: int = 16
    first_expert: int = 0
    router_hidden_size: int = 256
    # how far a step moves each balancing bias
    bias_update_rate: float = 1e-3
    seq_len: int = 8192
    learning_rate: float = 3e-4
    # steps over which the learning rate rises linearly from 0
    warmup_steps: int = 0
    max_grad_norm: float = 1.0
    # the dense flash kernels' tiles (queries, keys): of the eleven pairs
    # from 128 to 1,024 tried at 8,192 keys on the chip, the best forward
    # and within 2 % of the best backward (PERF.md, PR 31)
    flash_tiles: tuple = (512, 512)
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)


# the layers' leaves that a block multiplies in the compute dtype
MATRICES = ("wq", "wk", "wv1", "wv2", "wo", "conv1_w", "w_gate", "w_up",
            "w_down")


def tiny_config(**kw) -> ZayaConfig:
    defaults = dict(vocab_size=96, model_dim=32, num_layers=2, num_heads=4,
                    num_kv_heads=2, head_dim=16, num_experts=4,
                    expert_dim=24, experts_held=2, first_expert=0,
                    router_hidden_size=8, seq_len=16, num_partitions=1,
                    compute_dtype=jnp.float32)
    defaults.update(kw)
    return ZayaConfig(**defaults)


def _shift(a, steps: int = 1):
    """``a [B, T, ...]`` moved ``steps`` positions later along the
    sequence, zeros in front: position ``t`` reads ``t - steps``."""
    if steps == 0:
        return a
    pad = [(0, 0), (steps, 0)] + [(0, 0)] * (a.ndim - 2)
    return jnp.pad(a, pad)[:, :a.shape[1]]


def _unit_heads(x, eps):
    """Each head of ``x [..., d]`` brought to length ``sqrt(d)``, in
    float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                              + eps)


def partial_rope(x, theta: float, factor: float):
    """Rotary embedding on the first ``factor`` of each head of ``x [B,
    T, H, d]`` (float32), positions ``0 .. T - 1``: half-split layout
    inside the rotated part, pair ``i`` of ``n`` turning by ``t *
    theta^(-i / n)``; the rest of the head passes."""
    T, d = x.shape[1], x.shape[-1]
    rot = int(d * factor)
    n = rot // 2
    inv_freq = jnp.asarray(
        float(theta) ** (-np.arange(n, dtype=np.float64) / n), jnp.float32)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq   # [T, n]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2, rest = x[..., :n], x[..., n:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def cca_mix(cfg: ZayaConfig, p, u):
    """The attention's operands from the normalised stream ``u [B, T,
    D]``: ``q [B, T, Hq, d]``, ``k`` and ``v [B, T, Hkv, d]`` in the
    compute dtype (the module's docstring)."""
    dt = cfg.compute_dtype
    B, T, _ = u.shape
    Hq, Hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = Hq // Hkv
    f32 = jnp.float32
    q_lat = (u @ p["wq"].astype(dt)).reshape(B, T, Hq, d)
    k_lat = (u @ p["wk"].astype(dt)).reshape(B, T, Hkv, d)
    v = jnp.concatenate(
        [(u @ p["wv1"].astype(dt)).reshape(B, T, Hkv // 2, d),
         (_shift(u) @ p["wv2"].astype(dt)).reshape(B, T, Hkv - Hkv // 2,
                                                   d)], axis=2)
    z = jnp.concatenate([q_lat, k_lat], axis=2)            # [B, T, H, d]
    H = Hq + Hkv
    # depthwise, a weight a channel and tap (float32: a multiply-add)
    w0 = p["conv0_w"].reshape(cfg.cca_time0, H, d)
    zf = z.astype(f32)
    z1 = p["conv0_b"].reshape(H, d) + sum(
        _shift(zf, i) * w0[i] for i in range(cfg.cca_time0))
    # grouped, one [d, d] matrix a head and tap
    z1 = z1.astype(dt)
    # (a product a head and tap, [B * T, d] x [d, d]: the CPU's runtime
    # has no batched bfloat16 product into float32)
    a1 = p["conv1_w"].astype(dt)
    z2 = p["conv1_b"].reshape(H, d) + sum(
        jnp.stack([jnp.dot(zi[:, :, h], a1[h, i], preferred_element_type=f32)
                   for h in range(H)], axis=2)
        for i, zi in ((i, _shift(z1, i)) for i in range(cfg.cca_time1)))
    qf, kf = q_lat.astype(f32), k_lat.astype(f32)
    q_mean = jnp.mean(qf.reshape(B, T, Hkv, g, d), axis=3)
    q = z2[:, :, :Hq] + 0.5 * (qf + jnp.repeat(kf, g, axis=2))
    k = z2[:, :, Hq:] + 0.5 * (q_mean + kf)
    q = _unit_heads(q, cfg.rms_norm_eps)
    k = _unit_heads(k, cfg.rms_norm_eps) * p["tau"].astype(f32)[:, None]
    q = partial_rope(q, cfg.rope_theta, cfg.partial_rotary_factor)
    k = partial_rope(k, cfg.rope_theta, cfg.partial_rotary_factor)
    return q.astype(dt), k.astype(dt), v


def router(cfg: ZayaConfig, p, u, r_prev, beta):
    """The router on the normalised stream ``u [N, D]``: its new state
    ``r [N, R]`` (what the next layer receives), the probabilities ``[N,
    E]`` and ``p + beta``, all float32."""
    f32 = jnp.float32
    r = u.astype(f32) @ p["r_wd"].astype(f32) + p["r_bd"] \
        + p["r_gamma"] * r_prev
    hid = rms_norm(r, p["r_norm"], cfg.rms_norm_eps)
    hid = jax.nn.gelu(hid @ p["r_w1"] + p["r_b1"], approximate=False)
    hid = jax.nn.gelu(hid @ p["r_w2"] + p["r_b2"], approximate=False)
    probs = jax.nn.softmax(hid @ p["r_w3"], axis=-1)
    return r, probs, probs + jax.lax.stop_gradient(beta)


def _layer(cfg: ZayaConfig, p, beta, h, r_prev, impls=(None, None),
           forced_choice=None):
    """One block on the stream ``h [B, T, D]`` and the previous layer's
    router state ``r_prev [B * T, R]``. Returns ``(h, r)``, the layer's
    scalars (its experts' loads among them) and what it chose
    (``choice``, and ``margin``: the best ``p + beta`` less the second).
    ``forced_choice [B * T]`` takes the place of the router's choice."""
    dt = cfg.compute_dtype
    B, T, D = h.shape
    eps = cfg.rms_norm_eps

    # the layers' names in the compiled step (obs/xprof.LAYER_SCOPES);
    # the inner scope wins
    with jax.named_scope("attention"):
        u = rms_norm(h, p["ln1"], eps)
        with jax.named_scope("cca_mix"):
            q, k, v = cca_mix(cfg, p, u)
        o = attend(cfg, q, k, v, None, None, impls[0])
        h = h + o.reshape(B, T, -1) @ p["wo"].astype(dt)

    with jax.named_scope("moe"):
        u = rms_norm(h, p["ln2"], eps).reshape(B * T, D)
        with jax.named_scope("router"):
            r, probs, biased = router(cfg, p, u, r_prev, beta)
            # the argmax and its margin over the second (ties to the
            # lower index, as argmax)
            top2, order = jax.lax.top_k(biased, 2)
            own = order[:, 0].astype(jnp.int32)
            choice = own if forced_choice is None else forced_choice
            gate = jnp.take_along_axis(probs, choice[:, None], axis=-1)
            load = jnp.sum(jax.nn.one_hot(choice, cfg.num_experts,
                                          dtype=jnp.float32), axis=0)
        moe = moe_ops.routed_experts(
            u, choice[:, None], gate, p["w_gate"], p["w_up"], p["w_down"],
            num_experts=cfg.num_experts, first_expert=cfg.first_expert,
            impl=impls[1])
        h = h + moe.out.reshape(B, T, D)
    scalars = {"load": load, "gate_mean": jnp.mean(gate),
               **moe_ops.moe_scalars(moe)}
    picked = {"choice": own, "margin": top2[:, 0] - top2[:, 1]}
    return (h, r), scalars, picked


def balance_step(cfg: ZayaConfig, beta, load):
    """The balancing biases after a step that sent ``load [L, E]``
    tokens to each expert: ``ops/moe.balance_step`` at
    ``bias_update_rate``."""
    return moe_ops.balance_step(beta, load, cfg.bias_update_rate)


def init_params(cfg: ZayaConfig, rng):
    V, D, L = cfg.padded_vocab, cfg.model_dim, cfg.num_layers
    Hq, Hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    H, R = Hq + Hkv, cfg.router_hidden_size
    E, Eh, F = cfg.num_experts, cfg.experts_held, cfg.expert_dim
    t0, t1 = cfg.cca_time0, cfg.cca_time1
    ks = jax.random.split(rng, 16)
    # the convolutions start as the identity on tap 0 plus noise, so
    # that the latent passes and the previous token is felt
    conv0 = jnp.zeros((L, t0, H * d)).at[:, 0].set(1.0) \
        + 0.1 * jax.random.normal(ks[5], (L, t0, H * d))
    layers = {
        "ln1": jnp.ones((L, D)), "ln2": jnp.ones((L, D)),
        "wq": normal_init(ks[0], (L, D, Hq * d), D),
        "wk": normal_init(ks[1], (L, D, Hkv * d), D),
        "wv1": normal_init(ks[2], (L, D, (Hkv // 2) * d), D),
        "wv2": normal_init(ks[3], (L, D, (Hkv - Hkv // 2) * d), D),
        "wo": normal_init(ks[4], (L, Hq * d, D), Hq * d),
        "conv0_w": conv0, "conv0_b": jnp.zeros((L, H * d)),
        "conv1_w": normal_init(ks[6], (L, H, t1, d, d), t1 * d),
        "conv1_b": jnp.zeros((L, H * d)),
        "tau": jnp.ones((L, Hkv)),
        "r_wd": normal_init(ks[7], (L, D, R), D), "r_bd": jnp.zeros((L, R)),
        "r_gamma": jnp.ones((L, R)), "r_norm": jnp.ones((L, R)),
        "r_w1": normal_init(ks[8], (L, R, R), R), "r_b1": jnp.zeros((L, R)),
        "r_w2": normal_init(ks[9], (L, R, R), R), "r_b2": jnp.zeros((L, R)),
        "r_w3": normal_init(ks[10], (L, R, E), R),
        "w_gate": normal_init(ks[11], (L, Eh, D, F), D),
        "w_up": normal_init(ks[12], (L, Eh, D, F), D),
        "w_down": normal_init(ks[13], (L, Eh, F, D), F),
    }
    # rows at 1 / sqrt(D): the stream starts at unit scale behind the
    # sqrt(D) multiplier, and the tied head's logits at unit variance
    return {"emb": normal_init(ks[14], (V, D), D), "layers": layers,
            "final_norm": jnp.ones((D,))}


def forward(cfg: ZayaConfig, params, beta, batch, impls=(None, None)):
    """The model on ``batch``: ``(nll [B, T], per-layer scalars and
    choices stacked over the layers)``."""
    dt = cfg.compute_dtype
    x = batch["x"]
    B, T = x.shape
    D = cfg.model_dim
    h = (emb_ops.embedding_lookup(params["emb"], x)
         * np.sqrt(D)).astype(dt)
    r0 = jnp.zeros((B * T, cfg.router_hidden_size), jnp.float32)
    forced = batch.get("expert_choice")
    if forced is not None:
        forced = forced.reshape(cfg.num_layers, B * T).astype(jnp.int32)

    def body(carry, xs):
        p, beta_l, forced_l = xs
        return _layer(cfg, p, beta_l, *carry, impls, forced_l)

    def scanned(carry, xs):
        carry, scalars, picked = body(carry, xs)
        return carry, (scalars, picked)

    # what a rematerialised layer keeps for its backward pass: the
    # attention's output and logsumexp and the experts' row buffers (the
    # ops name them), so that no kernel runs a second time
    scanned = jax.checkpoint(
        scanned, policy=jax.checkpoint_policies.save_only_these_names(
            pa.KEPT, moe_ops.KEPT))
    # the scan's own operations (the matrices' cast, a layer's weights
    # cut out of the stack, its kept arrays and gradients written into
    # theirs, the carry `r`) go by this name; inside a block its layers'
    # names win
    with jax.named_scope("layer_scan"):
        layers = in_compute_dtype(params["layers"], MATRICES, dt)
        (h, _), (scalars, picked) = jax.lax.scan(
            scanned, (h, r0), (layers, beta, forced))

    # the tied head; the float32 logits of 8,192 tokens over 32,784 rows
    # are held whole: 1.07 GB, beside their cotangent
    nll = lm_head_nll(cfg, h, params["final_norm"], params["emb"].T,
                      batch["y"])
    return nll.reshape(B, T), scalars, picked


def build_model(cfg: ZayaConfig, impls=(None, None)) -> Model:
    E = cfg.num_experts
    moe_ops.check_held(E, cfg.experts_held, cfg.first_expert)
    if cfg.experts_per_token != 1:
        raise ValueError("the router chooses one expert a token")
    if cfg.num_heads % cfg.num_kv_heads or cfg.num_kv_heads % 2:
        raise ValueError("the value shift halves the key/value heads, "
                         "which group the query heads")

    def init_fn(rng):
        return init_params(cfg, rng), \
            {"beta": jnp.zeros((cfg.num_layers, E), jnp.float32)}

    def loss_fn(params, model_state, batch, rng):
        beta = model_state["beta"]
        nll, s, _ = forward(cfg, params, beta, batch, impls)
        loss = weighted_mean(nll, batch)
        new_beta = balance_step(cfg, beta,
                                jax.lax.stop_gradient(s["load"]))
        metrics = {
            "lm_loss": loss, **moe_ops.moe_metrics(s),
            "router_gate_mean": jnp.mean(s["gate_mean"]),
            "router_bias_abs_max": jnp.max(jnp.abs(new_beta))}
        return loss, metrics, {"beta": new_beta}

    return Model(init_fn, loss_fn, optimizer=clipped_adam(cfg), stateful=True,
                 gauges={**moe_ops.GAUGES,
                         "router.gate_mean": "router_gate_mean",
                         "router.bias_abs_max": "router_bias_abs_max"})
