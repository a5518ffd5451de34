"""The plain reference of ``olmo-hybrid-7b``: Olmo-Hybrid-7B
(``config.json``, ``model_type`` ``olmo_hybrid``: 32 layers,
``layer_types`` = (linear_attention x 3, full_attention) x 8, dense
SwiGLU MLPs), its loss and the gradients of that loss by ``jax.grad``, in
float32 ``jax.numpy`` with ``default_matmul_precision("highest")``: no
kernel, no chunk, no cache. Written from the equations below, not from
the program.

A block on the stream ``h_t`` in R^D (a matrix maps a row vector; norm is
RMSNorm with a learned weight)::

    h' = h + norm(mixer(h))          h'' = h' + norm(mlp(h'))
    mlp(x) = (silu(x W_gate) * (x W_up)) W_down

*Linear layer* (the gated delta rule, Yang, Kautz, Hatamizadeh,
arXiv:2412.06464), per held head of ``dk`` key and ``dv`` value entries::

    q = l2(silu(conv(h Wq))) / sqrt(dk)    k = l2(silu(conv(h Wk)))
    v = silu(conv(h Wv))
        conv(x)_t = sum_{i < taps} c[i] * x_{t-i}   (a weight a channel
        and tap, zeros before position 0);  l2(x) = x / sqrt(|x|^2 + 1e-6)
    beta_t = beta_scale * sigmoid(h_t Wb)         (2: allow_neg_eigval)
    g_t = -exp(A_log) softplus(h_t Wa + dt_bias)  alpha_t = exp(decay_on * g_t)
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S_0 = 0
    o_t = S_t q_t
    mixer(h) = concat_heads(norm_dv(o_t) * silu(h_t Wg)) Wo

THE RULE RUNS TOKEN BY TOKEN: a ``lax.scan`` over the positions applying
the recurrence as written, in segments of ``SEGMENT`` tokens each
rematerialised, so that its gradient keeps a state a segment and the
states of one segment (the system's chunked algebra is nowhere here: the
two are independent).

*Full layer*: ``q = norm(h Wq)``, ``k = norm(h Wk)`` over the whole
projected width (the held columns), ``v = h Wv``; no rotary embedding;
``o[t, j] = sum_{s <= t} softmax_s(q[t, j] . k[s, j] / sqrt(d)) v[s,
j]``, the queries in blocks of ``Q_BLOCK`` against all keys;
``mixer(h) = concat_heads(o) Wo``.

Final norm, the untied head over the vocabulary slice, the weighted mean
cross-entropy. The parameters are the chip's share (the held heads'
columns, the slice's rows); what the absent heads would add behind ``Wo``
is left out, as the system leaves it out.

``gates`` (``{"decay_on", "beta_scale"}``, float32 scalars, DATA of the
compiled program): the model's own are 1 and 2 (``model_gates``); 0 and
1 is a model WITHOUT its decay and its negative eigenvalues (the
negative control: ``alpha`` = 1, ``beta`` = sigmoid).

Departures from the published description: the configuration file's
``assumed`` has each with its reason.
"""

from __future__ import annotations

NEG = -1e30
Q_BLOCK = 256
SEGMENT = 64
MLP_ROW_BLOCKS = 4
LINEAR, FULL = "linear_attention", "full_attention"


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def l2(x):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv_silu(x, c):
    """``silu(sum_i c[i] * x[t - i])`` on ``x [B, T, N]``, ``c [taps,
    N]``."""
    import jax
    import jax.numpy as jnp
    T = x.shape[1]
    out = 0.0
    for i in range(c.shape[0]):
        out = out + jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :T] * c[i]
    return jax.nn.silu(out)


def delta_rule_by_token(q, k, v, alpha, beta):
    """``o [B, T, H, dv]`` of the recurrence, a token at a time; ``q, k
    [B, T, H, dk]``, ``v [B, T, H, dv]``, ``alpha, beta [B, T, H]``."""
    import jax
    import jax.numpy as jnp
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    seg = SEGMENT if T % SEGMENT == 0 else T

    def token(S, x):
        q_t, k_t, v_t, a_t, b_t = x                 # [B, H, ...]
        a_t, b_t = a_t[..., None, None], b_t[..., None, None]
        k_row = k_t[..., None, :]                   # [B, H, 1, dk]
        # S (I - beta k k^T) = S - beta (S k) k^T
        Sk = jnp.einsum("bhvk,bhk->bhv", S, k_t)[..., :, None]
        S = a_t * (S - b_t * Sk * k_row) + b_t * v_t[..., :, None] * k_row
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    def by_segment(a):
        a = jnp.moveaxis(a, 1, 0)                   # [T, B, H, ...]
        return a.reshape(T // seg, seg, *a.shape[1:])

    _, o = jax.lax.scan(segment, jnp.zeros((B, H, dv, dk), q.dtype),
                        tuple(by_segment(a)
                              for a in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o.reshape(T, B, H, dv), 0, 1)


def causal_attention(q, k, v):
    """``o [B, T, H, d]``, the queries in blocks of ``Q_BLOCK`` against
    all keys under the causal mask, each block rematerialised."""
    import jax
    import jax.numpy as jnp
    B, T, H, d = q.shape
    C = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(xs):
        q_c, start = xs
        logits = jnp.einsum("bqhd,bshd->bhqs", q_c, k) \
            * jnp.float32(d ** -0.5)
        seen = (start + jnp.arange(C))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, NEG), -1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    blocks = jnp.moveaxis(q.reshape(B, T // C, C, H, d), 1, 0)
    out = jax.lax.map(block, (blocks, C * jnp.arange(T // C)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, d)


def mlp(p, x):
    """The SwiGLU MLP on ``x [B, T, D]``, in row blocks each
    rematerialised (the ``[T, 11008]`` intermediates a block at a
    time)."""
    import jax
    B, T, D = x.shape
    n = MLP_ROW_BLOCKS if T % MLP_ROW_BLOCKS == 0 else 1

    @jax.checkpoint
    def rows(x_b):
        return (jax.nn.silu(x_b @ p["w_gate"]) * (x_b @ p["w_up"])) \
            @ p["w_down"]

    return jax.lax.map(rows, x.reshape(n, B * T // n, D)).reshape(B, T, D)


def _block(m, p, h, mixed):
    eps = m["rms_norm_eps"]
    h = h + rms_norm(mixed, p["mix_norm"], eps)
    return h + rms_norm(mlp(p, h), p["mlp_norm"], eps)


def linear_mixer(m, p, h, gates):
    """``(mixer(h), alpha [B, T, H], beta [B, T, H])`` of a linear
    layer."""
    import jax
    import jax.numpy as jnp
    B, T, _ = h.shape
    dk, dv = int(m["linear_key_head_dim"]), int(m["linear_value_head_dim"])
    q = l2(conv_silu(h @ p["wq"], p["conv_q"]).reshape(B, T, -1, dk)) \
        * dk ** -0.5
    k = l2(conv_silu(h @ p["wk"], p["conv_k"]).reshape(B, T, -1, dk))
    v = conv_silu(h @ p["wv"], p["conv_v"]).reshape(B, T, -1, dv)
    beta = gates["beta_scale"] * jax.nn.sigmoid(h @ p["wb"])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(h @ p["wa"] + p["dt_bias"])
    alpha = jnp.exp(gates["decay_on"] * g)
    o = delta_rule_by_token(q, k, v, alpha, beta)
    gate = jax.nn.silu(h @ p["wg"]).reshape(B, T, -1, dv)
    o = rms_norm(o, p["o_norm"], m["rms_norm_eps"]) * gate
    return o.reshape(B, T, -1) @ p["wo"], alpha, beta


def full_mixer(m, p, h):
    B, T, _ = h.shape
    d, eps = int(m["head_dim"]), m["rms_norm_eps"]
    q = rms_norm(h @ p["wq"], p["q_norm"], eps).reshape(B, T, -1, d)
    k = rms_norm(h @ p["wk"], p["k_norm"], eps).reshape(B, T, -1, d)
    v = (h @ p["wv"]).reshape(B, T, -1, d)
    return causal_attention(q, k, v).reshape(B, T, -1) @ p["wo"]


def _forward(m, params, batch, gates):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    params = jax.tree.map(lambda a: a.astype(f32), params)
    x, y, w = batch["x"], batch["y"], batch["w"]
    B, T = x.shape
    h = jnp.take(params["emb"], x, axis=0)

    # the layers one after another, in the order ``layer_types`` gives
    # them (the stacks' leading axes: [period, place] and [period]); each
    # rematerialised
    @jax.checkpoint
    def linear(h, p):
        mixed, alpha, beta = linear_mixer(m, p, h, gates)
        return _block(m, p, h, mixed), jnp.mean(alpha), jnp.mean(beta)

    @jax.checkpoint
    def full(h, p):
        return _block(m, p, h, full_mixer(m, p, h))

    periods, places = params["linear"]["wq"].shape[:2]
    assert list(m["layer_types"]) == [LINEAR] * places + [FULL], \
        m["layer_types"]
    alphas, betas = [], []
    for i in range(periods):
        for j in range(places):
            h, a, b = linear(h, jax.tree.map(lambda s: s[i, j],
                                             params["linear"]))
            alphas.append(a)
            betas.append(b)
        h = full(h, jax.tree.map(lambda s: s[i], params["full"]))
    hidden = rms_norm(h, params["final_norm"], m["rms_norm_eps"])
    logits = hidden.reshape(B * T, -1) @ params["head"]
    real = jnp.arange(logits.shape[1]) < m["vocab_size"]
    logits = jnp.where(real[None, :], logits, -jnp.inf)
    nll = jax.nn.logsumexp(logits, axis=1) \
        - jnp.take_along_axis(logits, y.reshape(-1, 1), axis=1)[:, 0]
    wf = w.reshape(-1)
    loss = jnp.sum(nll * wf) / jnp.sum(wf)
    return loss, {"nll": nll.reshape(B, T), "logits": logits, "loss": loss,
                  "decay_mean": jnp.mean(jnp.stack(alphas)),
                  "beta_mean": jnp.mean(jnp.stack(betas))}


def model_gates(m: dict, without_gates: bool = False) -> dict:
    """The ``gates`` of the model as published, or of a model WITHOUT
    its decay and its negative eigenvalues (the negative control)."""
    import numpy as np
    scale = 2.0 if m.get("linear_allow_neg_eigval", True) else 1.0
    return {"decay_on": np.float32(0.0 if without_gates else 1.0),
            "beta_scale": np.float32(1.0 if without_gates else scale)}


def forward(params, batch, model: dict, gates=None):
    """``(loss, outputs)`` of the whole model on ``batch`` (``x``, ``y``,
    ``w``); ``outputs`` holds ``nll [B, T]``, ``logits``, the loss and
    the linear layers' mean ``alpha`` and ``beta``."""
    import jax
    gates = model_gates(model) if gates is None else gates
    with jax.default_matmul_precision("highest"):
        return _forward(model, params, batch, gates)


# whose gradients are compared, ``<stack>/<leaf>`` (stacked over the
# stack's layers): the linear layers' query projection, the decay's path
# (``Wa``: 57,600 entries a layer, where ``A_log``'s 15 read 3-7 % from
# seed to seed under bfloat16), a convolution's weights; the full layers'
# query projection and their MLP's gate matrix; and the table
GRAD_ARRAYS = ("linear/wq", "linear/wa", "linear/conv_k", "full/wq",
               "full/w_gate")
TABLE = "emb"


def with_compared(params, sub):
    out = dict(params)
    out[TABLE] = sub[TABLE]
    for name in GRAD_ARRAYS:
        stack, leaf = name.split("/")
        out[stack] = {**out[stack], leaf: sub[name]}
    return out


def compared(params) -> dict:
    """The leaves ``GRAD_ARRAYS`` and ``TABLE`` name, out of ``params``."""
    sub = {name: params[name.split("/")[0]][name.split("/")[1]]
           for name in GRAD_ARRAYS}
    sub[TABLE] = params[TABLE]
    return sub


def loss_and_grads(params, batch, model: dict, gates=None, programs=None):
    """``(outputs, grads)``: ``forward``'s outputs without the logits,
    and the gradient of the loss with respect to ``compared(params)``, by
    ``jax.grad``. A caller that comes again with the same shapes passes
    the same dict as ``programs``: the compiled program is left there,
    and serves any ``gates``."""
    import json
    import time

    import jax
    m = dict(model)
    gates = model_gates(m) if gates is None else gates

    # the batch and the gates are arguments, not constants of the
    # program: one compiled program (and one entry of the compile cache)
    # for every seed and for the control
    def loss_of(sub, params, batch, gates):
        return _forward(m, with_compared(params, sub), batch, gates)

    sub = compared(params)
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                          (sub, params, batch, gates))
    key = json.dumps([m, str(shapes)], sort_keys=True, default=str)
    programs = {} if programs is None else programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        if key not in programs:
            programs[key] = jax.jit(jax.grad(loss_of, has_aux=True)).lower(
                sub, params, batch, gates).compile()
        t1 = time.perf_counter()
        grads, outputs = jax.block_until_ready(
            programs[key](sub, params, batch, gates))
        t2 = time.perf_counter()
    outputs.pop("logits")
    outputs["seconds"] = {"compile_or_load": round(t1 - t0, 2),
                          "run": round(t2 - t1, 2)}
    return outputs, grads


def train_matmul_flops_per_token(model: dict) -> int:
    """Matrix-product operations the forward and backward passes of the
    MODEL need for one trained token (3 x the forward's; nothing the
    implementation recomputes): per linear layer the q, k, v, gate and
    output projections and the two gates', and the rule's own products
    a token (``S k``, the rank-one update, ``S q``: 3 x 2 dk dv a head);
    per full layer the q, k, v, o projections and attention's two
    products over the causal pairs (averaged over ``seq_len``); the MLP
    of every layer; and the head over the slice."""
    m = model
    D, T, F = (int(m[k]) for k in ("model_dim", "seq_len",
                                   "intermediate_size"))
    H, d = int(m["num_heads"]), int(m["head_dim"])
    dk, dv = int(m["linear_key_head_dim"]), int(m["linear_value_head_dim"])
    mlp_flops = 3 * 2 * D * F
    linear = 2 * D * H * (2 * dk + 3 * dv + 2) + 3 * 2 * H * dk * dv
    full = 4 * 2 * D * H * d + 2 * 2 * H * d * (T + 1) / 2
    kinds = list(m["layer_types"])
    periods = int(m["num_layers"]) // len(kinds)
    layers = periods * sum((linear if kind == LINEAR else full) + mlp_flops
                           for kind in kinds)
    return int(3 * (layers + 2 * D * int(m["vocab_size"])))
