"""The plain reference of ``glm-4.7-flash``: GLM-4.7-Flash (zai-org,
``model_type`` ``glm4_moe_lite``; ``config.json``: 47 layers, the first
dense (``first_k_dense_replace`` 1, SwiGLU of 10,240), then 64 SwiGLU
experts of 1,536 top-4 under a sigmoid router with balancing biases
(``noaux_tc``, one group) beside 1 shared expert; multi-head latent
attention, 20 heads, ``q_lora_rank`` 768, ``kv_lora_rank`` 512, ``qk``
192 + 64 rotary, ``v`` 256; one multi-token-prediction layer), its loss,
the gradients of that loss by ``jax.grad``, the biases' rule and one
lazy Adam step of the table's touched rows, in
float32 ``jax.numpy`` with ``default_matmul_precision("highest")``: no
kernel, no grouped product, no cache. Written from the equations below
(those of DeepSeek-V3's architecture, which ``glm4_moe_lite`` shares),
not from the program.

For the stream ``h_t`` in R^D; ``H`` heads; a matrix maps a row vector;
``h_0 = emb[ids]``. Layer ``l``::

    a = RMSNorm(h)
    c_q = RMSNorm_q(a Wqa)                       [768]
    [q_nope_j | q_rot_j] = (c_q Wqb)_j           [192 | 64]  for each head j
    [c_kv | k_rot] = a Wkva                      [512 | 64]
    [k_nope_j | v_j] = (RMSNorm_kv(c_kv) Wkvb)_j [192 | 256]
    q_rot_j, k_rot = RoPE(q_rot_j), RoPE(k_rot)  one k_rot for every head
    s[t, s', j] = (q_nope_j[t] . k_nope_j[s'] + q_rot_j[t] . k_rot[s']) / sqrt(192 + 64)
    o_j[t] = sum_{s' <= t} softmax_{s'}(s[t, s', j]) v_j[s']
    h' = h + concat_j(o_j) Wo

``RoPE`` turns pair ``i`` (entries ``i`` and ``i + 32``: half-split) by
``t * theta^(-i / 32)``, theta 1e6, written as ``x cos + rotate_half(x)
sin``. Then in the first layer::

    m = RMSNorm(h');  h'' = h' + (silu(m Wg) * (m Wu)) Wd

and in the others, under the layer's balancing biases ``b [E]``::

    m = RMSNorm(h');  s = sigmoid(m Wr) over all E;  E_t = top4(s + b)
    w_e = 1.8 * s_e / (sum_{E_t} s + 1e-20)                   (without b)
    h'' = h' + (silu(m Sg) * (m Su)) Sd + sum_{e in E_t, e held} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e

Final RMSNorm, the untied head over the vocabulary slice. The MTP layer
(DeepSeek-V3's section 2.2, one module) at position ``t`` from the main
stream's last ``h_t`` (before the final norm) and the next token
``x_{t+1}``::

    u_t = [RMSNorm_e(emb[x_{t+1}]) ; RMSNorm_h(h_t)] Weh      (4,096 -> 2,048)
    g = one more expert layer on u;  logits = RMSNorm_f(g) head   (the SAME emb and head)

against ``x_{t+2}``, the last position weighing 0. The loss is the
weighted mean cross-entropy of the main stream plus 0.3 times the MTP
stream's; no auxiliary loss. After a step every expert layer's biases
(the MTP layer's last) move by ``load_balance_coeff`` against the sign
of each expert's load (the (token, choice) pairs it was sent, of all
``E`` experts) over the layer's mean. Every held expert is computed for
every token and masked; what the absent experts would add is left out,
as the system leaves it out; the shared expert is whole.

``choices [L_moe + 1, B, T, 4]`` (``batch["expert_choice"]``), where
given, take the place of ``E_t`` in every expert layer, gates and loads
with them: the comparison under ONE routing.

DEPARTURES from what ``config.json`` has a key for (the configuration
file's ``assumed`` has each with its reason):

1. the rotary dims in half-split layout (entries ``i``, ``i + 32``
   paired), not interleaved (``2 i``, ``2 i + 1``): with random weights
   the two differ by a fixed permutation of ``Wqb``'s and ``Wkva``'s
   rotary columns;
2. the MTP loss's weight 0.3 (DeepSeek-V3's report, section 4.2);
3. the MTP layer shares the embedding and the head (its checkpoint
   carries copies of both);
4. the biases' rule at a step of 0.001 (``noaux_tc`` names the rule,
   not its step); the deltas are not centred;
5. no sequence-wise auxiliary loss.

Of FORM, not of arithmetic: the expert layers, identical in shape, run
under one ``lax.scan``, each rematerialised; the causal attention is a
``[block, T]`` mask a block of ``Q_BLOCK`` queries (``lax.map``), so
that T = 8192 fits beside the training state; an expert at a time,
rematerialised.
"""

from __future__ import annotations

NEG = -1e30
Q_BLOCK = 256


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rotate(x, theta):
    """RoPE on ``x [B, T, H, 2n]`` (half-split), positions ``0 .. T -
    1``."""
    import jax.numpy as jnp
    n = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(n, dtype=jnp.float32) / n)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., n:], x[..., :n]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def causal_attention(q_nope, q_rot, k_nope, k_rot, v):
    """``o [B, T, H, dv]`` over the causal keys; ``k_rot [B, T, dr]`` is
    every head's. The queries in blocks of ``Q_BLOCK`` against all keys
    under the causal ``[block, T]`` mask."""
    import jax
    import jax.numpy as jnp
    B, T, H, _ = q_nope.shape
    scale = jnp.float32((q_nope.shape[-1] + q_rot.shape[-1]) ** -0.5)
    C = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(xs):
        qn, qr, start = xs
        logits = (jnp.einsum("bqhd,bshd->bhqs", qn, k_nope)
                  + jnp.einsum("bqhd,bsd->bhqs", qr, k_rot)) * scale
        seen = (start + jnp.arange(C))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, NEG), -1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    def blocks(x):
        return jnp.moveaxis(x.reshape(B, T // C, C, *x.shape[2:]), 1, 0)

    out = jax.lax.map(block, (blocks(q_nope), blocks(q_rot),
                              C * jnp.arange(T // C)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, v.shape[-1])


def attention(m, p, h):
    B, T, D = h.shape
    H = int(m["num_heads"])
    dn, dr, dv = (int(m[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                      "v_head_dim"))
    R, eps = int(m["kv_lora_rank"]), m["rms_norm_eps"]
    a = rms_norm(h, p["ln1"], eps)
    q = (rms_norm(a @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"]) \
        .reshape(B, T, H, dn + dr)
    kv = a @ p["wkv_a"]
    k_rot = rotate(kv[..., R:][:, :, None, :], m["rope_theta"])[:, :, 0]
    kv = (rms_norm(kv[..., :R], p["kv_a_norm"], eps) @ p["wkv_b"]) \
        .reshape(B, T, H, dn + dv)
    o = causal_attention(q[..., :dn], rotate(q[..., dn:], m["rope_theta"]),
                         kv[..., :dn], k_rot, kv[..., dn:])
    return h + o.reshape(B, T, H * dv) @ p["wo"]


def swiglu(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def dense_layer(m, p, h):
    h = attention(m, p, h)
    return h + swiglu(rms_norm(h, p["ln2"], m["rms_norm_eps"]), p["w_gate"],
                      p["w_up"], p["w_down"])


def route(m, x, router, bias, forced=None, fed=None):
    """``(chosen [N, k], gates [N, k], the router's own top-k, the
    scores [N, E], the mean of the chosen scores' sum)`` on the
    normalised rows ``x [N, D]``. ``forced [N, k]`` takes the place of
    the router's own top-k where ``fed`` (a traced 0 / 1) is 1."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(x @ router)
    own = jax.lax.top_k(scores + bias, int(m["experts_per_token"]))[1]
    chosen = own if forced is None else jnp.where(fed > 0, forced, own)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    gates = top / (total + 1e-20) if m["route_norm"] else top
    return chosen, float(m["route_scale"]) * gates, own, scores, \
        jnp.mean(total)


def expert_mix(m, p, bias, x, forced=None, fed=None):
    """The shared expert and the chosen experts among those
    ``p["w_gate"]`` holds (from ``first_expert`` on) on the normalised
    rows ``x [N, D]``, and what the router did."""
    import jax
    import jax.numpy as jnp
    chosen, gates, own, scores, gate_sum = route(m, x, p["router"], bias,
                                                 forced, fed)
    load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[1]), axis=(0, 1))
    out = swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"])
    expert = jax.checkpoint(swiglu)
    for e in range(p["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == m["first_expert"] + e, gates, 0.0),
                       axis=-1)
        out = out + mine[:, None] * expert(x, p["w_gate"][e], p["w_up"][e],
                                           p["w_down"][e])
    return out, {"expert_choice": own, "router_scores": scores,
                 "load": load, "gate_sum_mean": gate_sum}


def expert_layer(m, p, bias, h, forced=None, fed=None):
    B, T, D = h.shape
    h = attention(m, p, h)
    x = rms_norm(h, p["ln2"], m["rms_norm_eps"]).reshape(B * T, D)
    out, picked = expert_mix(m, p, bias, x, forced, fed)
    return h + out.reshape(B, T, D), picked


def cross_entropy(m, h, norm, head, labels):
    """Every position's negative log-likelihood ``[B * T]``."""
    import jax
    import jax.numpy as jnp
    hidden = rms_norm(h, norm, m["rms_norm_eps"])
    logits = hidden.reshape(-1, hidden.shape[-1]) @ head
    real = jnp.arange(logits.shape[1]) < m["vocab_size"]
    logits = jnp.where(real[None, :], logits, -jnp.inf)
    return jax.nn.logsumexp(logits, axis=1) \
        - jnp.take_along_axis(logits, labels.reshape(-1, 1), axis=1)[:, 0]


def _forward(m, params, bias, batch):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    params = jax.tree.map(lambda a: a.astype(f32), params)
    bias = bias.astype(f32)
    x, y, w = batch["x"], batch["y"], batch["w"]
    B, T = x.shape
    Ld = int(m["num_dense_layers"])
    L = int(m["num_layers"]) - Ld
    forced, fed = batch["expert_choice"], batch["expert_choice_fed"]
    forced = forced.reshape(L + 1, B * T, -1)
    h = jnp.take(params["emb"], x, axis=0)

    for i in range(Ld):
        h = jax.checkpoint(lambda p, h: dense_layer(m, p, h))(
            jax.tree.map(lambda a: a[i], params["dense"]), h)

    @jax.checkpoint
    def body(h, xs):
        p, bias_l, forced_l = xs
        return expert_layer(m, p, bias_l, h, forced_l, fed)

    h, picked = jax.lax.scan(body, h, (params["layers"], bias[:L],
                                       forced[:L]))
    nll = cross_entropy(m, h, params["final_norm"], params["head"], y)
    wf = w.reshape(-1)
    main = jnp.sum(nll * wf) / jnp.sum(wf)

    # the MTP layer: its input token at t is x_{t+1} = y_t, its label
    # y_{t+1}; the last position has none
    p = params["mtp"]
    e = rms_norm(jnp.take(params["emb"], y, axis=0), p["enorm"],
                 m["rms_norm_eps"])
    u = jnp.concatenate([e, rms_norm(h, p["hnorm"], m["rms_norm_eps"])],
                        axis=-1) @ p["w_eh"]
    g, mtp_picked = jax.checkpoint(
        lambda p, u: expert_layer(m, p, bias[L], u, forced[L], fed))(p, u)
    mtp_nll = cross_entropy(m, g, p["final_norm"], params["head"],
                            jnp.roll(y, -1, axis=1))
    mtp_w = jnp.roll(w, -1, axis=1).at[:, -1].set(0.0).reshape(-1)
    mtp = jnp.sum(mtp_nll * mtp_w) / jnp.sum(mtp_w)
    loss = main + float(m["mtp_loss_weight"]) * mtp
    picked = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]), picked,
                          mtp_picked)
    return loss, {"nll": nll.reshape(B, T), "mtp_nll": mtp_nll.reshape(B, T),
                  **picked}


def _fields(model: dict) -> dict:
    m = dict(model)
    m.setdefault("first_expert", 0)
    assert int(m.get("num_mtp_layers", 1)) == 1, m
    return m


def _fed(m, batch):
    """``batch`` with ``expert_choice`` and its switch always present:
    zeros and 0 where none was given."""
    import numpy as np
    fed = "expert_choice" in batch
    L = int(m["num_layers"]) - int(m["num_dense_layers"]) + 1
    return {**batch, "expert_choice_fed": np.float32(fed),
            "expert_choice": batch["expert_choice"] if fed else np.zeros(
                (L, *np.shape(batch["x"]), int(m["experts_per_token"])),
                np.int32)}


def forward(params, bias, batch, model: dict):
    """``(loss, outputs)`` of the whole model on ``batch`` (``x``, ``y``,
    ``w`` and optionally ``expert_choice [L_moe + 1, B, T, k]``) under
    the biases ``bias [L_moe + 1, E]``; ``outputs`` holds ``nll [B,
    T]``, ``mtp_nll [B, T]`` (at ``t`` against ``x_{t+2}``) and,
    stacked over the expert layers with the MTP layer's last,
    ``expert_choice`` (the router's own top-k, whatever was forced),
    ``router_scores`` (without the biases), ``load [L_moe + 1, E]`` and
    ``gate_sum_mean``."""
    import jax
    m = _fields(model)
    with jax.default_matmul_precision("highest"):
        return _forward(m, params, bias, _fed(m, batch))


def balance_step(bias, load, rate):
    """The biases after a step of loads ``load [L_moe + 1, E]``."""
    import numpy as np
    load = np.asarray(load, np.float64)
    return np.asarray(bias) - rate * np.sign(
        load - load.mean(axis=-1, keepdims=True))


# whose gradients are compared: of the expert layers (stacked over them)
# both latent paths' first and second products, the experts' and the
# shared expert's gate matrices and the router; of the dense layer the
# MLP's up matrix; of the MTP layer its input product; and the table
GRAD_ARRAYS = ("wq_a", "wkv_b", "w_gate", "shared_w_gate", "router")
DENSE_GRAD_ARRAYS = ("w_up",)
MTP_GRAD_ARRAYS = ("w_eh",)
TABLE = "emb"


def compared(params) -> dict:
    """The leaves whose gradients ``loss_and_grads`` returns: ``{name:
    leaf}``, the dense layer's under ``dense/<name>``, the MTP layer's
    under ``mtp/<name>``."""
    sub = {k: params["layers"][k] for k in GRAD_ARRAYS}
    sub.update({f"dense/{k}": params["dense"][k] for k in DENSE_GRAD_ARRAYS})
    sub.update({f"mtp/{k}": params["mtp"][k] for k in MTP_GRAD_ARRAYS})
    sub[TABLE] = params[TABLE]
    return sub


def with_compared(params, sub: dict) -> dict:
    """``params`` with ``compared``'s leaves replaced by ``sub``'s."""
    return {**params, TABLE: sub[TABLE],
            "layers": {**params["layers"],
                       **{k: sub[k] for k in GRAD_ARRAYS}},
            "dense": {**params["dense"],
                      **{k: sub[f"dense/{k}"] for k in DENSE_GRAD_ARRAYS}},
            "mtp": {**params["mtp"],
                    **{k: sub[f"mtp/{k}"] for k in MTP_GRAD_ARRAYS}}}


def loss_and_grads(params, bias, batch, model: dict, programs=None):
    """``(outputs, grads)``: ``forward``'s outputs and the gradient of
    the loss with respect to ``compared``'s leaves, by ``jax.grad``. A
    caller that comes again with the same shapes passes the same dict as
    ``programs``: the compiled program is left there, and serves a batch
    with or without a fed routing (the program always takes one, and a
    traced switch says whether it counts)."""
    import json
    import time

    import jax
    m = _fields(model)
    batch = _fed(m, batch)

    # the batch and the biases are arguments, not constants of the
    # program: one compiled program for every seed
    def loss_of(sub, params, bias, batch):
        return _forward(m, with_compared(params, sub), bias, batch)

    sub = compared(params)
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                          (sub, params, bias, batch))
    key = json.dumps([m, str(shapes)], sort_keys=True, default=str)
    programs = {} if programs is None else programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        if key not in programs:
            programs[key] = jax.jit(jax.grad(loss_of, has_aux=True)).lower(
                sub, params, bias, batch).compile()
        t1 = time.perf_counter()
        grads, outputs = jax.block_until_ready(
            programs[key](sub, params, bias, batch))
        t2 = time.perf_counter()
    outputs["seconds"] = {"compile_or_load": round(t1 - t0, 2),
                          "run": round(t2 - t1, 2)}
    return outputs, grads


# Adam's constants, the table's lazy Adam's too (the configuration's
# ``assumed``: the optimizer)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def lazy_adam_rows(rows, m, v, count: int, grad, rate: float):
    """One lazy Adam step (TF's ``LazyAdamOptimizer``) of a table's rows
    that a step touched: their moments take the step's gradient ``grad``
    (every lookup's rows summed), no other row's moments decay, and the
    bias corrections are those of the global step ``count + 1``. From
    ``rows``, ``m``, ``v`` and ``grad`` ``[n, D]`` before the step,
    ``(rows, m, v)`` after it in float64."""
    import numpy as np
    f64 = np.float64
    t = int(count) + 1
    g = np.asarray(grad, f64)
    m = ADAM_B1 * np.asarray(m, f64) + (1.0 - ADAM_B1) * g
    v = ADAM_B2 * np.asarray(v, f64) + (1.0 - ADAM_B2) * g * g
    step = rate * (m / (1.0 - ADAM_B1 ** t)) \
        / (np.sqrt(v / (1.0 - ADAM_B2 ** t)) + ADAM_EPS)
    return np.asarray(rows, f64) - step, m, v


def causal_pairs(T: int) -> int:
    """The (query, key) pairs of one head of a causal layer."""
    return int(T) * (int(T) + 1) // 2


def layer_flops(model: dict, dense: bool) -> float:
    """Forward matrix-product operations of one layer for one token: the
    latent attention's five products, its two products over the causal
    pairs (averaged over ``seq_len``; ``q . k`` over ``qk_nope +
    qk_rope``, ``p v`` over ``v``), and the dense MLP or the router, the
    shared expert and ``experts_per_token * experts_held / num_experts``
    routed experts."""
    m = model
    D, T, H = int(m["model_dim"]), int(m["seq_len"]), int(m["num_heads"])
    dn, dr, dv = (int(m[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                      "v_head_dim"))
    Rq, R = int(m["q_lora_rank"]), int(m["kv_lora_rank"])
    proj = 2 * (D * Rq + Rq * H * (dn + dr) + D * (R + dr)
                + R * H * (dn + dv) + H * dv * D)
    attention = 2 * H * (dn + dr + dv) * causal_pairs(T) / T
    if dense:
        return proj + attention + 3 * 2 * D * int(m["dense_mlp_dim"])
    F = int(m["expert_dim"])
    return proj + attention + 2 * D * int(m["num_experts"]) \
        + 3 * 2 * D * F * int(m["num_shared_experts"]) \
        + int(m["experts_per_token"]) * int(m["experts_held"]) \
        / int(m["num_experts"]) * 3 * 2 * D * F


def train_matmul_flops_per_token(model: dict) -> int:
    """Matrix-product operations the forward and backward passes of the
    MODEL need for one trained token (3 x the forward's; nothing the
    implementation recomputes or computes and masks): the dense layers,
    the expert layers, the MTP layers (each ``Weh`` and one expert
    layer), and a head over the slice for the main stream and each MTP
    layer."""
    m = model
    D = int(m["model_dim"])
    Ld = int(m["num_dense_layers"])
    L = int(m["num_layers"]) - Ld
    K = int(m.get("num_mtp_layers", 1))
    forward = Ld * layer_flops(m, True) + (L + K) * layer_flops(m, False) \
        + K * 2 * (2 * D) * D + (1 + K) * 2 * D * int(m["vocab_size"])
    return int(3 * forward)
