"""Olmo-Hybrid-7B — gated delta-rule (linear-attention) layers three to
one beside full-attention ones, every MLP a dense SwiGLU; a chip's share
of a layer's HEADS.

``config.json`` (``model_type`` ``olmo_hybrid``): ``layer_types`` =
(linear_attention x 3, full_attention) x 8. A block is ``h = h +
norm(mixer(h))``, ``h = h + norm(mlp(h))`` (the norm on each sub-block's
OUTPUT), of one of two kinds, whose WEIGHTS differ in shape:

*Linear layer*, per held head, ``dk`` = 96, ``dv`` = 192: ``q = h Wq``,
``k = h Wk``, ``v = h Wv``, each channel through a causal convolution of
``linear_conv_kernel_dim`` taps and SiLU (``ops/delta_rule.
causal_conv4_silu``); ``q`` and ``k`` L2-normalised over the head, ``q``
times ``dk ** -0.5``; ``beta = 2 sigmoid(h Wb)`` (the 2 is
``linear_allow_neg_eigval``), ``g = -exp(A_log) softplus(h Wa +
dt_bias)``, ``alpha = exp(g)``; the state ``S [dv, dk]`` a head, zero at
the sequence's start, ``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) +
beta_t v_t k_t^T``, ``o_t = S_t q_t`` (``ops/delta_rule.
gated_delta_rule``); ``y = concat_h(rms_norm(o; one weight of dv) *
silu(h Wg)) Wo``.

*Full layer*: ``q, k, v = h Wq, h Wk, h Wv`` without bias; RMSNorm over
the whole projected width of ``q`` and of ``k``; no rotary embedding
(``rope_theta`` null); causal softmax attention at ``head_dim ** -0.5``
(``ops/pallas_attention.flash_attention``); ``Wo``.

*MLP* (both kinds): ``(silu(x W_gate) * (x W_up)) W_down``, whole.

**The loop's body is a PERIOD.** The linear layers' parameters are
stacked ``[periods, 3, ...]``, the full layers' ``[periods, ...]``; the
outer ``lax.scan`` walks the periods, its body an inner ``lax.scan`` over
the period's linear layers and then the full one. The step so holds ONE
compiled body a kind of layer however deep the model (``PERF.md`` section
6, PR 32: L bodies of code were refused on the chip), and
``models/mellum2``'s one body with a ``cond`` could not hold two kinds of
weights. Each layer is rematerialised, keeping the flash kernels' output
and logsumexp and the rule's outputs, states and systems
(``ops/delta_rule.KEPT``) so that no kernel runs a second time, and two
of the MLP's three products (``MLP_KEPT``: the up projection's output
``[B, T, F]`` and the down product's ``[B, T, D]``, 0.97 GB in bfloat16
over the benchmark cell's four layers of 8,192 x 11,008) so that only
the gate's is made again. The down product's output is among them
because the norm stands on the sub-block's OUTPUT: its backward needs
``mlp(h)`` itself. The gate's pre-activation is not: with all three kept
(1.69 GB) the step fits the chip but the benchmark's comparison, which
evaluates this ``forward`` beside the whole training state and a rounded
copy of the weights, does not (``PERF.md`` section 6, PR 38). The
mixers' projections are still made again.

**The chip's share** (``PERF.md`` section 4): ``heads_held`` of the
layer's ``num_heads``, of both mixers alike (a unit is the full layer's
query / key / value head, or the linear layer's key head with its value
head: the published model has as many of each and no grouping). The
weights are made ``heads_held`` wide from the seed, so WHICH heads a chip
holds is nothing the program reads. Every operation before ``Wo`` is a
head's own but the full layer's QK-norm, whose statistic is then over the
held columns; ``Wo`` sums the held heads' part of the block's output, and
what the absent heads would add (the deployment's all-reduce) is left
out. ``held_share`` cuts a share's parameters out of the whole model's.

Untied head; the embedding is a gather-only table on the engine's slices
path (``SliceAdam``), everything else Adam behind a global-norm clip;
bfloat16 compute on float32 weights, the gates, the L2 and RMS norms'
statistics, the rule's state and the softmax in float32.

Batch contract as ``models/lm1b``: ``x``, ``y`` int32 ``[B, T]``, ``w``
float weights.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.engine import Model
from parallax_tpu.models.decoder import (  # noqa: F401
    FULL, MLP_KEPT, attend, clipped_adam, in_compute_dtype, lm_head_nll,
    make_batch, mlp, normal_init, rms_norm, weighted_mean)
from parallax_tpu.ops import delta_rule
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import pallas_attention as pa

LINEAR = "linear_attention"


@dataclasses.dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    model_dim: int = 3840
    num_layers: int = 32
    # the period: linear layers first, ONE full layer last
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    # of both mixers: ``num_attention_heads`` = ``num_key_value_heads`` =
    # ``linear_num_key_heads`` = ``linear_num_value_heads``
    num_heads: int = 30
    head_dim: int = 128
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-6
    # the chip's share of every layer's heads (None: all of them)
    heads_held: Optional[int] = None
    seq_len: int = 8192
    learning_rate: float = 3e-4
    # steps over which the learning rate rises linearly from 0
    warmup_steps: int = 0
    max_grad_norm: float = 1.0
    # the full layer's flash tiles (queries, keys)
    flash_tiles: tuple = (512, 512)
    num_partitions: Optional[int] = None
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)

    @property
    def held(self) -> int:
        return self.num_heads if self.heads_held is None \
            else self.heads_held

    @property
    def periods(self) -> int:
        """How many times ``layer_types`` repeats over the depth."""
        period = tuple(self.layer_types)
        if (len(period) < 2 or period[-1] != FULL
                or set(period[:-1]) != {LINEAR}
                or self.num_layers % len(period)):
            raise ValueError(
                f"layer_types {period} is no period of {self.num_layers} "
                f"layers: some {LINEAR}, then one {FULL}")
        return self.num_layers // len(period)


# the leaves a block multiplies in the compute dtype, by kind of layer
MLP_MATRICES = ("w_gate", "w_up", "w_down")
MATRICES = {LINEAR: ("wq", "wk", "wv", "wg", "wo", "wa", "wb")
            + MLP_MATRICES,
            FULL: ("wq", "wk", "wv", "wo") + MLP_MATRICES}
# where a leaf holds a size a head: (the heads' axis from the back, what
# one head takes of it), by kind of layer and the config's field
HEAD_AXES = {
    LINEAR: {"wq": (-1, "linear_key_head_dim"),
             "wk": (-1, "linear_key_head_dim"),
             "wv": (-1, "linear_value_head_dim"),
             "wg": (-1, "linear_value_head_dim"),
             "wo": (-2, "linear_value_head_dim"),
             "conv_q": (-1, "linear_key_head_dim"),
             "conv_k": (-1, "linear_key_head_dim"),
             "conv_v": (-1, "linear_value_head_dim"),
             "wa": (-1, None), "wb": (-1, None), "A_log": (-1, None),
             "dt_bias": (-1, None)},
    FULL: {"wq": (-1, "head_dim"), "wk": (-1, "head_dim"),
           "wv": (-1, "head_dim"), "wo": (-2, "head_dim"),
           "q_norm": (-1, "head_dim"), "k_norm": (-1, "head_dim")}}


def tiny_config(**kw) -> OlmoHybridConfig:
    """Two periods of (linear, linear, full) at toy widths, every head
    held."""
    defaults = dict(vocab_size=96, model_dim=32, num_layers=6,
                    layer_types=(LINEAR, LINEAR, FULL), num_heads=4,
                    head_dim=8, linear_key_head_dim=8,
                    linear_value_head_dim=16, intermediate_size=48,
                    seq_len=32, num_partitions=1,
                    compute_dtype=jnp.float32)
    defaults.update(kw)
    return OlmoHybridConfig(**defaults)


def _unit(x, eps=1e-6):
    """Each head of ``x [..., d]`` brought to unit length, in float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def linear_mixer(cfg: OlmoHybridConfig, p, x, impl=None):
    """A linear layer's mixer on ``x [B, T, D]``: ``(y [B, T, D], the
    mean of alpha, the mean of beta)``."""
    dt, f32 = cfg.compute_dtype, jnp.float32
    B, T, _ = x.shape
    H, dk, dv = cfg.held, cfg.linear_key_head_dim, cfg.linear_value_head_dim

    def short(name, conv):
        return delta_rule.causal_conv4_silu(x @ p[name].astype(dt), p[conv])

    q = _unit(short("wq", "conv_q").reshape(B, T, H, dk)) * dk ** -0.5
    k = _unit(short("wk", "conv_k").reshape(B, T, H, dk))
    v = short("wv", "conv_v").reshape(B, T, H, dv)
    step = jax.nn.sigmoid(jnp.dot(x, p["wb"].astype(dt),
                                  preferred_element_type=f32))
    beta = 2.0 * step if cfg.linear_allow_neg_eigval else step
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        jnp.dot(x, p["wa"].astype(dt), preferred_element_type=f32)
        + p["dt_bias"].astype(f32))
    with jax.named_scope("delta_rule"):
        o = delta_rule.gated_delta_rule(q.astype(dt), k.astype(dt), v, g,
                                        beta, impl=impl)
    gate = jax.nn.silu((x @ p["wg"].astype(dt)).astype(f32))
    o = rms_norm(o.astype(f32), p["o_norm"], cfg.rms_norm_eps) \
        * gate.reshape(B, T, H, dv)
    y = o.astype(dt).reshape(B, T, H * dv) @ p["wo"].astype(dt)
    return y, jnp.mean(jnp.exp(g)), jnp.mean(beta)


def full_qkv(cfg: OlmoHybridConfig, p, x):
    """A full layer's ``q, k, v [B, T, heads held, head_dim]`` from ``x
    [B, T, D]``, ``q`` and ``k`` behind their RMSNorm over the whole
    projected width: the columns held here."""
    dt, eps = cfg.compute_dtype, cfg.rms_norm_eps
    B, T, _ = x.shape
    q = rms_norm(x @ p["wq"].astype(dt), p["q_norm"], eps)
    k = rms_norm(x @ p["wk"].astype(dt), p["k_norm"], eps)
    v = x @ p["wv"].astype(dt)
    return tuple(a.reshape(B, T, -1, cfg.head_dim) for a in (q, k, v))


def full_attend(cfg: OlmoHybridConfig, p, q, k, v, impl=None):
    """Causal softmax attention without rotary embedding, and ``Wo``."""
    B, T, H, d = q.shape
    o = attend(cfg, q, k, v, None, None, impl)
    return o.reshape(B, T, H * d) @ p["wo"].astype(cfg.compute_dtype)


def full_mixer(cfg: OlmoHybridConfig, p, x, impl=None):
    """A full layer's mixer on ``x [B, T, D]``."""
    return full_attend(cfg, p, *full_qkv(cfg, p, x), impl)


def _block(cfg: OlmoHybridConfig, p, h, mixed):
    """``h + norm(mixed)`` and the MLP's half of a block."""
    eps = cfg.rms_norm_eps
    h = h + rms_norm(mixed, p["mix_norm"], eps)
    return h + rms_norm(mlp(p, h, cfg.compute_dtype), p["mlp_norm"], eps)


def linear_layer(cfg: OlmoHybridConfig, p, h, impl=None):
    """One linear block on ``h [B, T, D]``: the new ``h`` and the
    layer's scalars."""
    # the layers' names in the compiled step (obs/xprof.LAYER_SCOPES);
    # the rule's and the MLP's inner scopes win inside
    with jax.named_scope("linear_attention"):
        y, decay, beta = linear_mixer(cfg, p, h, impl)
    return _block(cfg, p, h, y), {"decay_mean": decay, "beta_mean": beta}


def full_layer(cfg: OlmoHybridConfig, p, h, impl=None):
    with jax.named_scope("attention"):
        y = full_mixer(cfg, p, h, impl)
    return _block(cfg, p, h, y)


def init_params(cfg: OlmoHybridConfig, rng):
    V, D, F = cfg.padded_vocab, cfg.model_dim, cfg.intermediate_size
    P, n_lin = cfg.periods, len(cfg.layer_types) - 1
    H, d = cfg.held, cfg.head_dim
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    taps = cfg.linear_conv_kernel_dim
    keys = iter(jax.random.split(rng, 32))

    def dense(lead, shape, fan_in):
        return normal_init(next(keys), lead + shape, fan_in)

    def uniform(lead, shape, low, high):
        return jax.random.uniform(next(keys), lead + shape, jnp.float32,
                                  low, high)

    def mlp_leaves(lead):
        return {"w_gate": dense(lead, (D, F), D),
                "w_up": dense(lead, (D, F), D),
                "w_down": dense(lead, (F, D), F),
                "mix_norm": jnp.ones(lead + (D,)),
                "mlp_norm": jnp.ones(lead + (D,))}

    lead = (P, n_lin)
    # the gates start as arXiv:2412.06464's reference implementation's:
    # A uniform in (0, 16); dt_bias the inverse softplus of a step
    # log-uniform in (0.001, 0.1)
    step = jnp.exp(uniform(lead, (H,), np.log(1e-3), np.log(1e-1)))
    bound = taps ** -0.5
    linear = {
        "wq": dense(lead, (D, H * dk), D), "wk": dense(lead, (D, H * dk), D),
        "wv": dense(lead, (D, H * dv), D), "wg": dense(lead, (D, H * dv), D),
        "wo": dense(lead, (H * dv, D), H * dv),
        "wa": dense(lead, (D, H), D), "wb": dense(lead, (D, H), D),
        "A_log": jnp.log(uniform(lead, (H,), 1e-4, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "conv_q": uniform(lead, (taps, H * dk), -bound, bound),
        "conv_k": uniform(lead, (taps, H * dk), -bound, bound),
        "conv_v": uniform(lead, (taps, H * dv), -bound, bound),
        "o_norm": jnp.ones(lead + (dv,)), **mlp_leaves(lead)}
    lead = (P,)
    full = {
        "wq": dense(lead, (D, H * d), D), "wk": dense(lead, (D, H * d), D),
        "wv": dense(lead, (D, H * d), D),
        "wo": dense(lead, (H * d, D), H * d),
        "q_norm": jnp.ones(lead + (H * d,)),
        "k_norm": jnp.ones(lead + (H * d,)), **mlp_leaves(lead)}
    # the embedding at unit scale, as the other cells'
    return {"emb": jax.random.normal(next(keys), (V, D)),
            "linear": linear, "full": full, "final_norm": jnp.ones((D,)),
            "head": dense((), (D, V), D)}


def held_share(cfg: OlmoHybridConfig, params, first_head: int,
               heads_held: int):
    """The parameters of the chip that holds ``heads_held`` heads from
    ``first_head`` on, cut out of the whole model's ``params`` (``cfg``
    with every head held): the head-wise leaves' columns (``Wo``'s
    rows), everything else as it is."""
    def cut(a, axis, size):
        axis += a.ndim
        a = a.reshape(a.shape[:axis] + (cfg.num_heads, size)
                      + a.shape[axis + 1:])
        a = jax.lax.slice_in_dim(a, first_head, first_head + heads_held,
                                 axis=axis)
        return a.reshape(a.shape[:axis] + (heads_held * size,)
                         + a.shape[axis + 2:])

    out = dict(params)
    for kind, name in ((LINEAR, "linear"), (FULL, "full")):
        leaves = dict(params[name])
        for leaf, (axis, field) in HEAD_AXES[kind].items():
            size = 1 if field is None else getattr(cfg, field)
            leaves[leaf] = cut(leaves[leaf], axis, size)
        out[name] = leaves
    return out


def forward(cfg: OlmoHybridConfig, params, batch, impls=(None, None)):
    """The model on ``batch``: ``(nll [B, T], the linear layers' scalars
    stacked [periods, 3])``. ``impls``: the executors of the full
    layer's attention and of the rule (None: by the backend)."""
    dt = cfg.compute_dtype
    h = emb_ops.embedding_lookup(params["emb"], batch["x"]).astype(dt)

    # what a rematerialised layer keeps for its backward pass: the
    # kernels' outputs, so that no kernel runs a second time, and the
    # MLP's up product and its output (the norm behind it needs that
    # one), so that of its three products the gate's alone does: 243 MB
    # a layer at 8,192 x 11,008, 0.97 GB over the benchmark cell's four
    keep = jax.checkpoint_policies.save_only_these_names(
        pa.KEPT, delta_rule.KEPT, MLP_KEPT)
    one_linear = jax.checkpoint(
        lambda h, p: linear_layer(cfg, p, h, impls[1]), policy=keep)
    one_full = jax.checkpoint(
        lambda h, p: full_layer(cfg, p, h, impls[0]), policy=keep)

    def period(h, xs):
        p_linear, p_full = xs
        h, scalars = jax.lax.scan(one_linear, h, p_linear)
        return one_full(h, p_full), scalars

    # the scans' own operations (the matrices' cast, a layer's weights
    # cut out of their stacks, its kept arrays and gradients written
    # into theirs, the loops) go by this name; inside a block its
    # layers' names win
    with jax.named_scope("layer_scan"):
        stacks = (in_compute_dtype(params["linear"], MATRICES[LINEAR], dt),
                  in_compute_dtype(params["full"], MATRICES[FULL], dt))
        h, scalars = jax.lax.scan(period, h, stacks)

    nll = lm_head_nll(cfg, h, params["final_norm"], params["head"],
                      batch["y"])
    return nll.reshape(batch["x"].shape), scalars


def build_model(cfg: OlmoHybridConfig, impls=(None, None)) -> Model:
    cfg.periods     # a layer_types that is no period is refused here
    if not 1 <= cfg.held <= cfg.num_heads:
        raise ValueError(f"{cfg.held} heads held of the layer's "
                         f"{cfg.num_heads}")

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng):
        nll, s = forward(cfg, params, batch, impls)
        loss = weighted_mean(nll, batch)
        return loss, {"lm_loss": loss,
                      "linear_decay_mean": jnp.mean(s["decay_mean"]),
                      "linear_beta_mean": jnp.mean(s["beta_mean"])}

    from parallax_tpu.ops.sparse_optim import SliceAdam
    return Model(init_fn, loss_fn, optimizer=clipped_adam(cfg),
                 slice_updaters={"emb": SliceAdam(cfg.learning_rate)},
                 gauges={"linear_attn.decay_mean": "linear_decay_mean",
                         "linear_attn.beta_mean": "linear_beta_mean"})
