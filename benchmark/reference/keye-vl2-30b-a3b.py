"""The plain reference of ``keye-vl2-30b-a3b``: the language model of
Keye-VL-2.0-30B-A3B (``config.json``: 48 identical layers of grouped-query
attention under the ``sa_config`` indexer's top-2048 key selection and 128
routed SwiGLU experts top-8), its three-part loss and the gradients of
that loss by ``jax.grad``, in float32 ``jax.numpy`` with
``default_matmul_precision("highest")``: no kernel, no grouped product,
no cache. The vision tower is not built (the catalog's ``config`` gives
none of its sizes): text traffic, the same position in all three streams
unless a batch brings its own.

One layer, for ``h_t`` in R^D and positions ``pos_t`` in N^3::

    x  = RMSNorm(h)
    q  = RoPE3(RMSNorm_head(x Wq))  [Hq, 128]     k likewise [Hkv, 128]
    v  = x Wv                       [Hkv, 128]
    qI = RoPE3(x WqI) [Hi, 64]   kI = RoPE3(x WkI) [64]   w = x Ww [Hi]
    I[t, s] = Hi^-1/2 * 64^-1/2 * sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
    S_t = the topk keys of largest I[t, .], ties to the lower s (lax.top_k)
    o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, h // (Hq / Hkv)] / sqrt(128)) v[s, ...]
    h' = h + concat(o) Wo
    y  = RMSNorm(h');  p = softmax(y Wr) over all E;  E_t = top-8 of p
    g_e = p_e / sum_{E_t} p
    h'' = h' + sum_{e in E_t, e held} g_e Wdown_e (silu(Wgate_e y) * Wup_e y)

``RoPE3``: half-split rotation, pair ``i`` of ``n`` turning by
``pos[stream(i)] * theta^(-i / n)``, the streams' pairs counted by
``mrope_section`` (scaled to the head's pairs). After the last layer
``RMSNorm`` and the head over the vocabulary slice. Every held expert is
computed for every token and masked; what the absent experts would add
is left out, as the system leaves it out (the deployment's other chips
hold them).

Loss: the mean cross-entropy, plus ``router_aux_loss_coef`` times the
layers' mean load-balance loss ``E * sum_e f_e * mean_t p_e``, plus
``indexer_loss_weight`` times the mean over queries and layers of
``KL(P_t || softmax_{S_t}(I[t, .]))``, ``P_t`` the attention's
probabilities over ``S_t`` summed over the heads and normalised, with
``P_t`` and the indexer's input ``x`` under ``stop_gradient``.

Departures from the published description: the config says nothing of
QK-norm, of the indexer's RoPE and scales, or of training; each is the
configuration file's ``assumed``. The identical layers run under one
``lax.scan``; queries are taken in blocks of ``q_chunk_size`` and each
block and layer is rematerialised for the gradient (``lax.map`` over
the blocks), so that T = 8192 fits beside the training state; a
block's scores are the dense ``[block, T]`` array
and its selection is ``jax.lax.top_k``, taken once for all blocks of a
layer before the attention (a selection carries no gradient).
"""

from __future__ import annotations


NEG = -1e30


def _fields(model: dict) -> dict:
    m = dict(model)
    m.setdefault("first_expert", 0)
    return m


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope3(x, pos, theta, section):
    """``x [B, T, ..., 2n]`` rotated in half-split layout by the three
    position streams ``pos [B, T, 3]``; ``section`` counts the pairs of
    each stream and is scaled to ``n`` pairs."""
    import jax.numpy as jnp
    import numpy as np
    n = x.shape[-1] // 2
    total = sum(section)
    counts = [c * n // total for c in section]
    assert sum(counts) == n, (section, n)
    stream = np.repeat(np.arange(3), counts)                    # [n]
    inv_freq = theta ** (-np.arange(n, dtype=np.float64) / n)
    angle = pos[..., stream].astype(jnp.float32) \
        * jnp.asarray(inv_freq, jnp.float32)                    # [B, T, n]
    while angle.ndim < x.ndim:
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :n], x[..., n:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def indexer_scores(qi, ki, wi):
    import jax
    import jax.numpy as jnp
    hi, di = qi.shape[-2], qi.shape[-1]
    z = jnp.einsum("bqjd,bsd->bqjs", qi, ki)
    s = jnp.sum(wi[..., None] * jax.nn.relu(z), axis=2) \
        * jnp.float32(hi ** -0.5 * di ** -0.5)
    return jnp.where(s == 0, jnp.float32(0), s)


def _select_block(qi, ki, wi, q_start, topk):
    """``S_t`` of the queries ``[q_start, q_start + C)`` among all ``T``
    keys, as booleans ``[B, C, T]``: ``jax.lax.top_k`` over the causal
    scores. No gradient passes through a selection."""
    import jax
    import jax.numpy as jnp
    B, C = qi.shape[:2]
    T = ki.shape[1]
    scores = indexer_scores(qi, ki, wi)
    causal = (jnp.arange(T)[None, :] <= q_start + jnp.arange(C)[:, None])[None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(int(topk), T))                    # [B, C, kk]
    return jnp.zeros((B, C, T), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(C)[None, :, None],
        idx].set(True) & causal


def _attention_block(q, k, v, qi, ki, wi, sel):
    """A block of queries against all ``T`` keys under its selection."""
    import jax
    import jax.numpy as jnp
    Hq, D = q.shape[2], q.shape[3]
    Hkv = k.shape[2]
    scores = indexer_scores(qi, ki, wi)                         # [B, C, T]
    kr = jnp.repeat(k, Hq // Hkv, axis=2)
    vr = jnp.repeat(v, Hq // Hkv, axis=2)
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kr) * jnp.float32(D ** -0.5)
    probs = jax.nn.softmax(jnp.where(sel[:, None], logits, NEG), axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, vr)
    target = jax.lax.stop_gradient(jnp.sum(probs, axis=1) / Hq)
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, NEG), axis=-1)
    kl = jnp.sum(jnp.where(
        sel & (target > 0),
        target * (jnp.log(jnp.maximum(target, 1e-37)) - log_q), 0.0))
    return out, kl, scores


def _layer(m, p, h, pos, collect):
    import jax
    import jax.numpy as jnp
    B, T, D = h.shape
    Hq, Hkv, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    Hi, Di = m["indexer_heads"], m["indexer_head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    section = m["mrope_section"]

    x = rms_norm(h, p["ln1"], eps)
    q = rms_norm((x @ p["wq"]).reshape(B, T, Hq, Dh), p["q_norm"], eps)
    k = rms_norm((x @ p["wk"]).reshape(B, T, Hkv, Dh), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(B, T, Hkv, Dh)
    q, k = rope3(q, pos, theta, section), rope3(k, pos, theta, section)
    xi = jax.lax.stop_gradient(x)
    qi = rope3((xi @ p["idx_wq"]).reshape(B, T, Hi, Di), pos, theta, section)
    ki = rope3(xi @ p["idx_wk"], pos, theta, section)
    wi = xi @ p["idx_ww"]

    C = min(int(m["q_chunk_size"]), T)

    def blocks(a):                       # [B, T, ...] -> [T / C, B, C, ...]
        return jnp.moveaxis(a.reshape((B, T // C, C) + a.shape[2:]), 1, 0)

    def unblock(a):
        return jnp.moveaxis(a, 0, 1).reshape((B, T) + a.shape[3:])

    starts = C * jnp.arange(T // C)
    # the selections first, once: constants of everything that follows
    sel = jax.lax.stop_gradient(jax.lax.map(
        lambda xs: _select_block(xs[0], ki, xs[1], xs[2],
                                 m["indexer_topk"]),
        (blocks(qi), blocks(wi), starts)))
    block = jax.checkpoint(_attention_block)
    o, kl, scores = jax.lax.map(
        lambda xs: block(xs[0], k, v, xs[1], ki, xs[2], xs[3]),
        (blocks(q), blocks(qi), blocks(wi), sel))
    kl = jnp.sum(kl)
    extra = {"selection": unblock(sel), "scores": unblock(scores)} \
        if collect else {}
    attn = unblock(o).reshape(B, T, Hq * Dh)
    h = h + attn @ p["wo"]

    y = rms_norm(h, p["ln2"], eps).reshape(B * T, D)
    probs = jax.nn.softmax(y @ p["router"], axis=-1)            # [N, E]
    E, kx = probs.shape[1], int(m["experts_per_token"])
    top_p, top_i = jax.lax.top_k(probs, kx)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    density = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, E), axis=1), axis=0) / kx
    aux = E * jnp.sum(density * jnp.mean(probs, axis=0))
    held = p["w_gate"].shape[0]
    # every held expert for every token, then the token's own gates
    act = jax.nn.silu(jnp.einsum("nd,edf->nef", y, p["w_gate"])) \
        * jnp.einsum("nd,edf->nef", y, p["w_up"])
    each = jnp.einsum("nef,efd->ned", act, p["w_down"])         # [N, held, D]
    weight = jnp.sum(
        jax.nn.one_hot(top_i - m["first_expert"], held) * gates[..., None],
        axis=1)                                                  # [N, held]
    h = h + jnp.einsum("ned,ne->nd", each, weight).reshape(B, T, D)
    if collect:
        extra.update(router_probs=probs, expert_choice=top_i)
    return h, kl, aux, extra


def _forward(m, params, x, y, w, pos, collect_layer=None):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    params = jax.tree.map(lambda a: a.astype(f32), params)
    B, T = x.shape
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(T)[None, :, None], (B, T, 3))
    h = jnp.take(params["emb"], x, axis=0)
    L = params["layers"]["wq"].shape[0]
    collect = collect_layer is not None

    # the layers are identical, so one body under a scan: a quarter of
    # the program to compile, the same arithmetic
    @jax.checkpoint
    def body(h, p):
        h, kl, aux, extra = _layer(m, p, h, pos, collect)
        return h, (kl, aux, extra)

    h, (kl, aux, extras) = jax.lax.scan(body, h, params["layers"])
    kl_total, aux_total = jnp.sum(kl), jnp.sum(aux)
    collected = {k: v[collect_layer] for k, v in extras.items()}
    hidden = rms_norm(h, params["final_norm"], m["rms_norm_eps"])
    logits = hidden.reshape(B * T, -1) @ params["head"]
    real = jnp.arange(logits.shape[1]) < m["vocab_size"]
    logits = jnp.where(real[None, :], logits, -jnp.inf)
    nll = jax.nn.logsumexp(logits, axis=1) \
        - jnp.take_along_axis(logits, y.reshape(-1, 1), axis=1)[:, 0]
    wf = w.reshape(-1)
    lm_loss = jnp.sum(nll * wf) / jnp.sum(wf)
    indexer_loss = kl_total / (L * B * T)
    aux_loss = aux_total / L
    loss = lm_loss + m["router_aux_loss_coef"] * aux_loss \
        + m["indexer_loss_weight"] * indexer_loss
    return loss, {"nll": nll.reshape(B, T), "logits": logits,
                  "lm_loss": lm_loss, "aux_loss": aux_loss,
                  "indexer_loss": indexer_loss, **collected}


def forward(params, batch, model: dict, collect_layer=None):
    """``(loss, outputs)`` of the whole model on ``batch`` (``x``, ``y``,
    ``w`` and optionally ``pos [B, T, 3]``); ``outputs`` holds ``nll [B,
    T]``, ``logits``, the loss's three parts and, for ``collect_layer``,
    that layer's ``selection [B, T, T]``, ``scores``, ``router_probs``
    and ``expert_choice``."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _forward(_fields(model), params, batch["x"], batch["y"],
                        batch["w"], batch.get("pos"), collect_layer)


GRAD_ARRAYS = ("wq", "w_gate", "idx_wq")


def loss_and_grads(params, batch, model: dict, collect_layer=0,
                   programs=None):
    """``(outputs, grads)``: ``forward``'s outputs and the gradient of
    the three-part loss with respect to the layers' ``GRAD_ARRAYS``
    (stacked over layers), by ``jax.grad``. A caller that comes again
    with the same shapes passes the same dict as ``programs``: the
    compiled program is left there."""
    import json
    import time

    import jax
    m = _fields(model)

    # the batch is an argument, not a constant of the program: one
    # compiled program (and one entry of the compile cache) for every seed
    def loss_of(sub, params, batch):
        layers = {**params["layers"], **sub}
        return _forward(m, {**params, "layers": layers}, batch["x"],
                        batch["y"], batch["w"], batch.get("pos"),
                        collect_layer)

    sub = {k: params["layers"][k] for k in GRAD_ARRAYS}
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                          (sub, params, batch))
    key = json.dumps([m, collect_layer, str(shapes)], sort_keys=True,
                     default=str)
    programs = {} if programs is None else programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        if key not in programs:
            programs[key] = jax.jit(jax.grad(loss_of, has_aux=True)).lower(
                sub, params, batch).compile()
        t1 = time.perf_counter()
        grads, outputs = jax.block_until_ready(
            programs[key](sub, params, batch))
        t2 = time.perf_counter()
    outputs.pop("logits")
    # how long the reference took to compile (or load) and to run, for
    # the detail line
    outputs["seconds"] = {"compile_or_load": round(t1 - t0, 2),
                          "run": round(t2 - t1, 2)}
    return outputs, grads


def train_matmul_flops_per_token(model: dict) -> int:
    """Matrix-product operations the forward and backward passes of the
    MODEL need for one trained token (3 x the forward's; nothing the
    implementation recomputes or computes and masks): per layer the
    q, k, v, o and the indexer's three projections, the indexer's
    scores over a query's ``t + 1`` causal keys and the attention's two
    products over its ``min(t + 1, topk)`` selected keys, averaged over
    ``seq_len``; the router; ``experts_per_token * experts_held /
    num_experts`` experts a token; and the head over the slice."""
    m = model
    D, T = int(m["model_dim"]), int(m["seq_len"])
    Hq, Hkv, Dh = (int(m[k]) for k in ("num_heads", "num_kv_heads",
                                       "head_dim"))
    Hi, Di = int(m["indexer_heads"]), int(m["indexer_head_dim"])
    topk = int(m["indexer_topk"])
    proj = 2 * D * (2 * Hq * Dh + 2 * Hkv * Dh)
    indexer_proj = 2 * D * (Hi * Di + Di + Hi)
    mean_causal = (T + 1) / 2
    mean_selected = sum(min(t + 1, topk) for t in range(T)) / T
    scores = 2 * Hi * Di * mean_causal
    attention = 2 * 2 * Hq * Dh * mean_selected
    router = 2 * D * int(m["num_experts"])
    experts = int(m["experts_per_token"]) * int(m["experts_held"]) \
        / int(m["num_experts"]) * 3 * 2 * D * int(m["expert_dim"])
    head = 2 * D * int(m["vocab_size"])
    layer = proj + indexer_proj + scores + attention + router + experts
    return int(3 * (int(m["num_layers"]) * layer + head))
