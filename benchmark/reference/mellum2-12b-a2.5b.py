"""The plain reference of ``mellum2-12b-a2.5b``: Mellum2-12B-A2.5B
(``config.json``: 28 layers, ``layer_types`` = (sliding, sliding,
sliding, full) x 7, a window of 1,024, a RoPE by layer type, 64 SwiGLU
experts of width 896 top-8), its loss and the gradients of that loss by
``jax.grad``, in float32 ``jax.numpy`` with
``default_matmul_precision("highest")``: no kernel, no grouped product,
no cache. Written from the equations below, not from the program.

Layer ``l`` of kind ``c = layer_types[l]``, for the stream ``h_t`` in
R^D; ``Hq`` query heads, ``Hkv`` key/value heads, ``g = Hq / Hkv``, head
size ``d`` with ``n = d / 2`` pairs; a matrix maps a row vector::

    x = RMSNorm(h)
    q = RoPE_c(RMSNorm_head(x Wq))  [Hq, d]     k likewise  [Hkv, d]
    v = x Wv                        [Hkv, d]
    o[t, j] = sum_{s: 0 <= t - s < W_c} softmax_s(q[t, j] . k[s, j // g] / sqrt(d)) v[s, j // g]
    h' = h + concat_j(o) Wo
    y  = RMSNorm(h');  p = softmax(y Wr) over all E;  E_t = the 8 largest of p
    g_e = p_e / sum_{E_t} p
    h'' = h' + sum_{e in E_t, e held} g_e (silu(y Wg_e) * (y Wu_e)) Wd_e

``W_c``: ``sliding_window`` on a sliding layer (the token itself and the
``W - 1`` before it), every causal key on a full one. ``RoPE_c`` turns
pair ``i`` of a head (entries ``i`` and ``i + n``: half-split) by ``t *
w_c[i]`` and multiplies ``cos`` and ``sin`` by ``a_c``::

    sliding (rope_type default):  w[i] = theta^(-i / n)            a = 1
    full (rope_type yarn, as transformers' _compute_yarn_parameters):
        d(r)    = 2 n ln(original_max_position / (2 pi r)) / (2 ln theta)
        low     = floor(d(beta_fast))     high = ceil(d(beta_slow))
        ramp[i] = clip((i - low) / (high - low), 0, 1)
        w[i]    = theta^(-i / n) * ((1 - ramp[i]) + ramp[i] / factor)
        a       = attention_factor  (= 0.1 ln(factor) + 1)

so a full layer's logits carry ``a^2``. Final RMSNorm, the untied head
over the vocabulary slice, the weighted mean cross-entropy plus
``router_aux_loss_coef`` times the layers' mean load-balance loss ``E *
sum_e f_e * mean_t p_e``. Every held expert is computed for every token
and masked; what the absent experts would add is left out, as the
system leaves it out (the deployment's other chips hold them).

``choices [L, B, T, 8]`` (``batch["expert_choice"]``), where given, take
the place of ``E_t`` in every layer, gates and loads with them: the
comparison under ONE routing.

Departures from the published description (the configuration file's
``assumed`` has each with its reason): the per-head RMSNorm on ``q`` and
``k``; no multi-token-prediction head (``config`` has no key for it).
Each layer's kind reaches the program as DATA (``layer_tables``: ``w``,
``a`` and ``W_c`` a layer, ``T`` standing for "every causal key"): the
identical layers run under one ``lax.scan``, each rematerialised, and
one compiled program serves the configuration and the control that
reads every layer as full with the default RoPE. The band is a ``[block,
T]`` mask a block of ``Q_BLOCK`` queries (``lax.map``), each block
rematerialised, so that T = 8192 fits beside the training state; an
expert at a time, rematerialised.
"""

from __future__ import annotations

import math

NEG = -1e30
Q_BLOCK = 256
SLIDING, FULL = "sliding_attention", "full_attention"


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def layer_kinds(m: dict) -> list:
    """Each layer's kind: ``layer_types`` repeated over ``num_layers``."""
    period = list(m["layer_types"])
    assert int(m["num_layers"]) % len(period) == 0, (period, m["num_layers"])
    return period * (int(m["num_layers"]) // len(period))


def yarn(m: dict):
    """``(low, high, w [n], a)`` of a full layer's RoPE."""
    import numpy as np
    n = int(m["head_dim"]) // 2
    theta, factor = float(m["rope_theta"]), float(m["yarn_factor"])
    ctx = float(m["yarn_original_max_position"])

    def d(turns):
        return 2 * n * math.log(ctx / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(d(float(m["yarn_beta_fast"]))), 0)
    high = min(math.ceil(d(float(m["yarn_beta_slow"]))), 2 * n - 1)
    i = np.arange(n, dtype=np.float64)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    w = theta ** (-i / n) * ((1.0 - ramp) + ramp / factor)
    a = m.get("yarn_attention_factor")
    return low, high, w, float(0.1 * math.log(factor) + 1.0 if a is None
                               else a)


def layer_tables(m: dict, every_layer_full_default_rope: bool = False):
    """What tells a layer its kind, as arrays over the layers:
    ``rope_w [L, n]``, ``rope_a [L]`` and ``window [L]`` (``seq_len``
    and more is every causal key). With the flag, the tables of a model
    WITHOUT its two kinds (the negative control): every layer full, the
    default RoPE."""
    import numpy as np
    n = int(m["head_dim"]) // 2
    plain = float(m["rope_theta"]) ** (-np.arange(n, dtype=np.float64) / n)
    _, _, w_full, a_full = yarn(m)
    far = 2 ** 30
    rows = []
    for kind in layer_kinds(m):
        if every_layer_full_default_rope:
            rows.append((plain, 1.0, far))
        elif kind == SLIDING:
            rows.append((plain, 1.0, int(m["sliding_window"])))
        else:
            assert kind == FULL, kind
            rows.append((w_full, a_full, far))
    w, a, window = zip(*rows)
    return {"rope_w": np.asarray(w, np.float32),
            "rope_a": np.asarray(a, np.float32),
            "window": np.asarray(window, np.int32)}


def rope(x, w, a):
    """``x [B, T, H, 2n]``: pair ``i`` (entries ``i``, ``i + n``) turned
    by ``t * w[i]``, ``cos`` and ``sin`` times ``a``."""
    import jax.numpy as jnp
    n = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * w
    cos = (a * jnp.cos(angle))[None, :, None, :]
    sin = (a * jnp.sin(angle))[None, :, None, :]
    x1, x2 = x[..., :n], x[..., n:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def banded_attention(q, k, v, window):
    """``o [B, T, Hq, d]``: query ``t`` over the keys ``s`` with ``0 <=
    t - s < window`` (a traced scalar), the queries in blocks of
    ``Q_BLOCK`` against all keys under the band's ``[block, T]``
    mask."""
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
        behind = (start + jnp.arange(C))[:, None] - jnp.arange(T)[None, :]
        seen = (behind >= 0) & (behind < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], logits, NEG), -1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, vr)

    blocks = jnp.moveaxis(q.reshape(B, T // C, C, Hq, d), 1, 0)
    out = jax.lax.map(block, (blocks, C * jnp.arange(T // C)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, Hq, d)


def _layer(m, p, kind, h, forced):
    import jax
    import jax.numpy as jnp
    B, T, D = h.shape
    Hq, Hkv, d = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]

    x = rms_norm(h, p["ln1"], eps)
    q = rms_norm((x @ p["wq"]).reshape(B, T, Hq, d), p["q_norm"], eps)
    k = rms_norm((x @ p["wk"]).reshape(B, T, Hkv, d), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(B, T, Hkv, d)
    q = rope(q, kind["rope_w"], kind["rope_a"])
    k = rope(k, kind["rope_w"], kind["rope_a"])
    o = banded_attention(q, k, v, kind["window"])
    h = h + o.reshape(B, T, Hq * d) @ p["wo"]

    y = rms_norm(h, p["ln2"], eps).reshape(B * T, D)
    probs = jax.nn.softmax(y @ p["router"], axis=-1)            # [N, E]
    E, kx = probs.shape[1], int(m["experts_per_token"])
    own = jax.lax.top_k(probs, kx)[1]
    chosen = own if forced is None else forced
    top_p = jnp.take_along_axis(probs, chosen, axis=-1)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    density = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, E), axis=1),
                       axis=0) / kx
    aux = E * jnp.sum(density * jnp.mean(probs, axis=0))

    @jax.checkpoint
    def expert(y, w_gate, w_up, w_down):
        return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down

    out = jnp.zeros_like(y)
    for e in range(p["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == m["first_expert"] + e, gates, 0.0),
                       axis=-1)
        out = out + mine[:, None] * expert(y, p["w_gate"][e], p["w_up"][e],
                                           p["w_down"][e])
    h = h + out.reshape(B, T, D)
    return h, aux, {"expert_choice": own, "router_probs": probs}


def _forward(m, params, batch, tables):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    params = jax.tree.map(lambda a: a.astype(f32), params)
    x, y, w = batch["x"], batch["y"], batch["w"]
    B, T = x.shape
    L = params["layers"]["wq"].shape[0]
    forced = batch.get("expert_choice")
    if forced is not None:
        forced = forced.reshape(L, B * T, -1)
    h = jnp.take(params["emb"], x, axis=0)

    # the layers have the same shapes and their kind is data, so one
    # body under a scan: a quarter of the program to compile, the same
    # arithmetic
    @jax.checkpoint
    def body(h, xs):
        p, kind, forced_l = xs
        h, aux, picked = _layer(m, p, kind, h, forced_l)
        return h, (aux, picked)

    h, (aux, picked) = jax.lax.scan(
        body, h, (params["layers"], tables, forced))
    hidden = rms_norm(h, params["final_norm"], m["rms_norm_eps"])
    logits = hidden.reshape(B * T, -1) @ params["head"]
    real = jnp.arange(logits.shape[1]) < m["vocab_size"]
    logits = jnp.where(real[None, :], logits, -jnp.inf)
    nll = jax.nn.logsumexp(logits, axis=1) \
        - jnp.take_along_axis(logits, y.reshape(-1, 1), axis=1)[:, 0]
    wf = w.reshape(-1)
    lm_loss = jnp.sum(nll * wf) / jnp.sum(wf)
    aux_loss = jnp.mean(aux)
    loss = lm_loss + m["router_aux_loss_coef"] * aux_loss
    return loss, {"nll": nll.reshape(B, T), "logits": logits,
                  "lm_loss": lm_loss, "aux_loss": aux_loss, **picked}


def _fields(model: dict) -> dict:
    m = dict(model)
    m.setdefault("first_expert", 0)
    return m


def _device_tables(tables: dict) -> dict:
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in tables.items()}


def forward(params, batch, model: dict, tables=None):
    """``(loss, outputs)`` of the whole model on ``batch`` (``x``, ``y``,
    ``w`` and optionally ``expert_choice [L, B, T, k]``); ``outputs``
    holds ``nll [B, T]``, ``logits``, the loss's two parts and, stacked
    over the layers, ``expert_choice`` (the router's own top-k, whatever
    was forced) and ``router_probs``. ``tables``: ``layer_tables``'
    (None: the model's own)."""
    import jax
    m = _fields(model)
    tables = _device_tables(layer_tables(m) if tables is None else tables)
    with jax.default_matmul_precision("highest"):
        return _forward(m, params, batch, tables)


# whose gradients are compared: of the layers (stacked over them) the
# queries' projection and the experts' gate matrices; and the table
GRAD_ARRAYS = ("wq", "w_gate")
TABLE = "emb"


def loss_and_grads(params, batch, model: dict, tables=None, programs=None):
    """``(outputs, grads)``: ``forward``'s outputs without the logits,
    and the gradient of the loss with respect to the layers'
    ``GRAD_ARRAYS`` (stacked over layers) and to the table (under
    ``TABLE``), by ``jax.grad``. A caller that comes again with the same
    shapes passes the same dict as ``programs``: the compiled program is
    left there, and serves any ``tables``."""
    import json
    import time

    import jax
    m = _fields(model)
    tables = _device_tables(layer_tables(m) if tables is None else tables)

    # the batch and the tables are arguments, not constants of the
    # program: one compiled program (and one entry of the compile cache)
    # for every seed and for the control
    def loss_of(sub, params, batch, tables):
        layers = {**params["layers"], **{k: sub[k] for k in GRAD_ARRAYS}}
        return _forward(m, {**params, TABLE: sub[TABLE], "layers": layers},
                        batch, tables)

    sub = {k: params["layers"][k] for k in GRAD_ARRAYS}
    sub[TABLE] = params[TABLE]
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                          (sub, params, batch, tables))
    key = json.dumps([m, str(shapes)], sort_keys=True, default=str)
    programs = {} if programs is None else programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        if key not in programs:
            programs[key] = jax.jit(jax.grad(loss_of, has_aux=True)).lower(
                sub, params, batch, tables).compile()
        t1 = time.perf_counter()
        grads, outputs = jax.block_until_ready(
            programs[key](sub, params, batch, tables))
        t2 = time.perf_counter()
    outputs.pop("logits")
    outputs["seconds"] = {"compile_or_load": round(t1 - t0, 2),
                          "run": round(t2 - t1, 2)}
    return outputs, grads


def attended_pairs(T: int, window: int) -> int:
    """The (query, key) pairs of one head of a layer whose queries read
    the ``window`` keys up to themselves: ``W (W + 1) / 2`` for the
    first ``W`` queries, ``W`` for each of the others."""
    W = min(int(window), int(T))
    return W * (W + 1) // 2 + (T - W) * W


def train_matmul_flops_per_token(model: dict) -> int:
    """Matrix-product operations the forward and backward passes of the
    MODEL need for one trained token (3 x the forward's; nothing the
    implementation recomputes or computes and masks): per layer the q,
    k, v, o projections, attention's two products over ITS pairs (a
    sliding layer's band, a full layer's causal triangle, averaged over
    ``seq_len``), the router, ``experts_per_token * experts_held /
    num_experts`` experts a token; and the head over the slice."""
    m = model
    D, T = int(m["model_dim"]), int(m["seq_len"])
    Hq, Hkv, Dh = (int(m[k]) for k in ("num_heads", "num_kv_heads",
                                       "head_dim"))
    proj = 2 * D * (2 * Hq * Dh + 2 * Hkv * Dh)
    router = 2 * D * int(m["num_experts"])
    experts = int(m["experts_per_token"]) * int(m["experts_held"]) \
        / int(m["num_experts"]) * 3 * 2 * D * int(m["expert_dim"])
    layers = 0.0
    for kind in layer_kinds(m):
        window = int(m["sliding_window"]) if kind == SLIDING else T
        attention = 2 * 2 * Hq * Dh * attended_pairs(T, window) / T
        layers += proj + attention + router + experts
    head = 2 * D * int(m["vocab_size"])
    return int(3 * (layers + head))
