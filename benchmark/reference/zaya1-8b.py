"""The plain reference of ``zaya1-8b``: ZAYA1-8B (``config.json``: 40
identical layers of attention in a compressed latent behind two causal
convolutions, CCA, and 16 SwiGLU experts of width 2048 chosen top-1 by an
MLP router that carries its state from layer to layer and is balanced by
biases; one tied table), its loss and the gradients of that loss by
``jax.grad``, in float32 ``jax.numpy`` with
``default_matmul_precision("highest")``: no kernel, no grouped product,
no cache. Written from the equations below, not from the program.

One layer, for the stream ``x_t`` in R^D, the previous layer's router
state ``r'_t`` in R^R (zero before the first layer) and this layer's
balancing biases ``beta`` in R^E; ``Hq`` query heads, ``Hkv`` key/value
heads, ``g = Hq / Hkv``, head size ``d``; a matrix maps a row vector,
``y = x W``::

    u   = RMSNorm(x)
    q~  = u Wq  [Hq, d]        k~ = u Wk  [Hkv, d]
    v_t = [u_t Wv1 ; u_{t-1} Wv2]  [Hkv, d]: the first Hkv / 2 heads from
          this token, the others from the previous one (u_{-1} = 0)
    z   = [q~ ; k~]  [Hq + Hkv, d]
    z'_t[h]  = b0[h] + sum_i a[i][h] * z_{t-i}[h]     i < cca_time0, elementwise
    z''_t[h] = b1[h] + sum_i z'_{t-i}[h] A[h, i]      i < cca_time1, A[h, i] [d, d]
    q_t[h] = z''_t[h] + (q~_t[h] + k~_t[h // g]) / 2
    k_t[j] = z''_t[Hq + j] + (mean_{h // g = j} q~_t[h] + k~_t[j]) / 2
    q <- RoPE(sqrt(d) q / |q|)     k <- RoPE(tau_j sqrt(d) k / |k|)
    o[t, h] = sum_{s <= t} softmax_s(q[t, h] . k[s, h // g] / sqrt(d)) v[s, h // g]
    x' = x + concat_h(o) Wo

    u  = RMSNorm(x')
    r  = u Wd + bd + gamma * r'                  (what the next layer receives)
    p  = softmax(gelu(gelu(RMSNorm(r) W1 + b1) W2 + b2) W3)    over all E
    e* = argmax(p + beta)        gate = p[e*]    (not renormalised)
    x'' = x' + gate * (silu(u Wgate[e*]) * (u Wup[e*])) Wdown[e*]   if e* is held
          x'                                                        if not

``sqrt(d) q / |q|`` is ``q / sqrt(mean(q^2) + eps)``. ``RoPE`` turns
the first ``partial_rotary_factor * d`` entries of a head, half-split:
pair ``i`` of ``n`` by ``t * theta^(-i / n)``. ``gelu`` is the exact one
(erf). Before the first layer ``x_0 = sqrt(D) * emb[id]``; after the
last, RMSNorm and the logits ``x emb^T`` over the vocabulary slice; the
loss is the weighted mean cross-entropy, no auxiliary term. After a
step the biases move: ``beta_e -= rate * sign(load_e - mean load)``
(``balance_step``). Every held expert is computed for every token and
masked; what the absent experts would add is left out, as the system
leaves it out (the deployment's other chip holds them).

``choices [L, B * T]`` (``batch["expert_choice"]``), where given, take
the place of ``e*`` in every layer: the comparison under ONE routing.

Departures from the published description (the configuration file's
``assumed`` has each with its source): biases on both convolutions,
where ``tau`` sits, ``gamma`` a vector started at 1, the bias rule and
its rate, the table's ``sqrt(D)`` multiplier, exact gelu. Not built: a
router output that skips the layer and learned residual scales
(``config`` has no key for either). The identical layers run under one
``lax.scan``, each rematerialised for the gradient; attention takes its
queries in blocks of 512 against all keys (``lax.map``), each block
rematerialised, so that T = 8192 fits beside the training state; an
expert at a time, rematerialised.
"""

from __future__ import annotations


NEG = -1e30
Q_BLOCK = 512


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def shift(a, steps):
    """Position ``t`` of the result is position ``t - steps`` of ``a [B,
    T, ...]``; zeros before the sequence."""
    import jax.numpy as jnp
    if steps == 0:
        return a
    return jnp.concatenate(
        [jnp.zeros_like(a[:, :steps]), a[:, :a.shape[1] - steps]], axis=1)


def rope(x, theta, factor):
    """``x [B, T, H, d]``: the first ``factor * d`` of each head turned
    by the position, half-split."""
    import jax.numpy as jnp
    import numpy as np
    T, d = x.shape[1], x.shape[-1]
    rot = int(d * factor)
    n = rot // 2
    inv_freq = float(theta) ** (-np.arange(n, dtype=np.float64) / n)
    angle = np.arange(T, dtype=np.float64)[:, None] * inv_freq   # [T, n]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    a, b = x[..., :n], x[..., n:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def attention_operands(m, p, u):
    """``q [B, T, Hq, d]``, ``k``, ``v [B, T, Hkv, d]`` of the CCA
    block from the normalised stream."""
    import jax.numpy as jnp
    B, T, _ = u.shape
    Hq, Hkv, d = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    g, H = Hq // Hkv, Hq + Hkv
    eps = m["rms_norm_eps"]
    q_lat = (u @ p["wq"]).reshape(B, T, Hq, d)
    k_lat = (u @ p["wk"]).reshape(B, T, Hkv, d)
    v = jnp.concatenate([(u @ p["wv1"]).reshape(B, T, -1, d),
                         (shift(u, 1) @ p["wv2"]).reshape(B, T, -1, d)],
                        axis=2)
    z = jnp.concatenate([q_lat, k_lat], axis=2)
    a = p["conv0_w"].reshape(-1, H, d)
    z1 = p["conv0_b"].reshape(H, d)
    for i in range(int(m["cca_time0"])):
        z1 = z1 + a[i] * shift(z, i)
    z2 = p["conv1_b"].reshape(H, d)
    for i in range(int(m["cca_time1"])):
        z2 = z2 + jnp.einsum("bthd,hde->bthe", shift(z1, i),
                             p["conv1_w"][:, i])
    q = z2[:, :, :Hq] + (q_lat + jnp.repeat(k_lat, g, axis=2)) / 2
    k = z2[:, :, Hq:] + (q_lat.reshape(B, T, Hkv, g, d).mean(axis=3)
                         + k_lat) / 2

    def unit(x):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    q = unit(q)
    k = unit(k) * p["tau"][:, None]
    theta, factor = m["rope_theta"], m["partial_rotary_factor"]
    return rope(q, theta, factor), rope(k, theta, factor), v


def causal_attention(q, k, v):
    """``o [B, T, Hq, d]``, the queries in blocks of ``Q_BLOCK``."""
    import jax
    import jax.numpy as jnp
    B, T, Hq, d = q.shape
    g = Hq // k.shape[2]
    kr, vr = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    C = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(xs):
        q_c, start = xs
        logits = jnp.einsum("bqhd,bshd->bhqs", q_c, kr) \
            * jnp.float32(d ** -0.5)
        seen = (jnp.arange(T)[None, :] <= start + jnp.arange(C)[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, NEG), -1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, vr)

    blocks = jnp.moveaxis(q.reshape(B, T // C, C, Hq, d), 1, 0)
    out = jax.lax.map(block, (blocks, C * jnp.arange(T // C)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, Hq, d)


def route(m, p, u, r_prev, beta):
    """``(r, probs [N, E], p + beta)`` of the router on ``u [N, D]``."""
    import jax
    r = u @ p["r_wd"] + p["r_bd"] + p["r_gamma"] * r_prev
    hid = rms_norm(r, p["r_norm"], m["rms_norm_eps"])
    hid = jax.nn.gelu(hid @ p["r_w1"] + p["r_b1"], approximate=False)
    hid = jax.nn.gelu(hid @ p["r_w2"] + p["r_b2"], approximate=False)
    probs = jax.nn.softmax(hid @ p["r_w3"], axis=-1)
    return r, probs, probs + beta


def _layer(m, p, beta, x, r_prev, forced):
    import jax
    import jax.numpy as jnp
    B, T, D = x.shape
    eps = m["rms_norm_eps"]
    u = rms_norm(x, p["ln1"], eps)
    q, k, v = attention_operands(m, p, u)
    x = x + causal_attention(q, k, v).reshape(B, T, -1) @ p["wo"]

    u = rms_norm(x, p["ln2"], eps).reshape(B * T, D)
    r, probs, biased = route(m, p, u, r_prev, beta)
    best = jax.lax.top_k(biased, 2)[0]
    own = jnp.argmax(biased, axis=-1)
    choice = own if forced is None else forced
    gate = jnp.take_along_axis(probs, choice[:, None], axis=-1)[:, 0]

    @jax.checkpoint
    def expert(u, w_gate, w_up, w_down):
        return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down

    held = p["w_gate"].shape[0]
    out = jnp.zeros_like(u)
    for e in range(held):
        mine = (choice == m["first_expert"] + e)
        out = out + jnp.where(mine, gate, 0.0)[:, None] * expert(
            u, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    x = x + out.reshape(B, T, D)
    load = jnp.sum(jax.nn.one_hot(choice, probs.shape[1]), axis=0)
    return x, r, {"choice": own, "margin": best[:, 0] - best[:, 1],
                  "gate": gate, "load": load}


def _forward(m, params, beta, batch):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    params = jax.tree.map(lambda a: a.astype(f32), params)
    x, y, w = batch["x"], batch["y"], batch["w"]
    B, T = x.shape
    D = params["emb"].shape[1]
    L = params["layers"]["wq"].shape[0]
    forced = batch.get("expert_choice")
    if forced is not None:
        forced = forced.reshape(L, B * T)
    h = jnp.take(params["emb"], x, axis=0) * jnp.float32(D ** 0.5)
    r = jnp.zeros((B * T, m["router_hidden_size"]), f32)

    # the layers are identical, so one body under a scan: a sixth of
    # the program to compile, the same arithmetic
    @jax.checkpoint
    def body(carry, xs):
        p, beta_l, forced_l = xs
        h, r, picked = _layer(m, p, beta_l, *carry, forced_l)
        return (h, r), picked

    (h, _), picked = jax.lax.scan(
        body, (h, r), (params["layers"], beta.astype(f32), forced))
    hidden = rms_norm(h, params["final_norm"], m["rms_norm_eps"])
    logits = hidden.reshape(B * T, D) @ params["emb"].T
    real = jnp.arange(logits.shape[1]) < m["vocab_size"]
    logits = jnp.where(real[None, :], logits, -jnp.inf)
    nll = jax.nn.logsumexp(logits, axis=1) \
        - jnp.take_along_axis(logits, y.reshape(-1, 1), axis=1)[:, 0]
    wf = w.reshape(-1)
    loss = jnp.sum(nll * wf) / jnp.sum(wf)
    return loss, {"nll": nll.reshape(B, T), "logits": logits, "loss": loss,
                  **picked}


def forward(params, batch, model: dict, beta):
    """``(loss, outputs)`` of the whole model on ``batch`` (``x``, ``y``,
    ``w`` and optionally ``expert_choice [L, B, T]``) under the biases
    ``beta [L, E]``; ``outputs`` holds ``nll [B, T]``, ``logits`` and,
    stacked over the layers, ``choice`` (the router's own ``argmax``,
    whatever was forced), ``margin`` (the best ``p + beta`` less the
    second), ``gate`` and ``load [L, E]``."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _forward(dict(model), params, beta, batch)


def balance_step(beta, load, rate):
    """The biases after a step of loads ``load [L, E]``."""
    import numpy as np
    load = np.asarray(load, np.float64)
    return np.asarray(beta) - rate * np.sign(
        load - load.mean(axis=-1, keepdims=True))


# whose gradients are compared: of the layers (stacked over them) the
# query latent's projection, the grouped convolution and the experts'
# gate matrices; and the tied table, whose gradient is the sum of the
# lookup's scatter-add and the head's ``[V, D]`` product
GRAD_ARRAYS = ("wq", "conv1_w", "w_gate")
TABLE = "emb"


def loss_and_grads(params, batch, model: dict, beta, programs=None):
    """``(outputs, grads)``: ``forward``'s outputs without the logits,
    and the gradient of the loss with respect to the layers'
    ``GRAD_ARRAYS`` (stacked over layers) and to the table (under
    ``TABLE``), by ``jax.grad``. A caller that comes again with the same
    shapes passes the same dict as ``programs``: the compiled program is
    left there."""
    import json
    import time

    import jax
    m = dict(model)

    # the batch is an argument, not a constant of the program: one
    # compiled program (and one entry of the compile cache) for every seed
    def loss_of(sub, params, beta, batch):
        layers = {**params["layers"], **{k: sub[k] for k in GRAD_ARRAYS}}
        return _forward(m, {**params, TABLE: sub[TABLE], "layers": layers},
                        beta, batch)

    sub = {k: params["layers"][k] for k in GRAD_ARRAYS}
    sub[TABLE] = params[TABLE]
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                          (sub, params, beta, batch))
    key = json.dumps([m, str(shapes)], sort_keys=True, default=str)
    programs = {} if programs is None else programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        if key not in programs:
            programs[key] = jax.jit(jax.grad(loss_of, has_aux=True)).lower(
                sub, params, beta, batch).compile()
        t1 = time.perf_counter()
        grads, outputs = jax.block_until_ready(
            programs[key](sub, params, beta, batch))
        t2 = time.perf_counter()
    outputs.pop("logits")
    outputs["seconds"] = {"compile_or_load": round(t1 - t0, 2),
                          "run": round(t2 - t1, 2)}
    return outputs, grads


def train_matmul_flops_per_token(model: dict) -> int:
    """Matrix-product operations the forward and backward passes of the
    MODEL need for one trained token (3 x the forward's; nothing the
    implementation recomputes or computes and masks): per layer the
    four projections of the CCA block (query latent, key latent, the two
    value halves, the output), the grouped convolution's ``cca_time1``
    products a head, attention's two products over a query's ``t + 1``
    causal keys averaged over ``seq_len``, the router (its projection
    and three matrices), ``experts_held / num_experts`` of an expert a
    token; and the tied head over the slice."""
    m = model
    D, T = int(m["model_dim"]), int(m["seq_len"])
    Hq, Hkv, d = (int(m[k]) for k in ("num_heads", "num_kv_heads",
                                      "head_dim"))
    R, E = int(m["router_hidden_size"]), int(m["num_experts"])
    proj = 2 * D * (2 * Hq * d + 2 * Hkv * d)
    conv = 2 * int(m["cca_time1"]) * (Hq + Hkv) * d * d
    attention = 2 * 2 * Hq * d * (T + 1) / 2
    router = 2 * (D * R + 2 * R * R + R * E)
    experts = int(m["experts_held"]) / E * 3 * 2 * D * int(m["expert_dim"])
    head = 2 * D * int(m["vocab_size"])
    layer = proj + conv + attention + router + experts
    return int(3 * (int(m["num_layers"]) * layer + head))
