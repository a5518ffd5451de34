"""The plain reference of ``trinity-mini``: Trinity-Mini (arcee-ai,
``model_type`` ``afmoe``; ``config.json``: 32 layers, ``layer_types`` =
(sliding, sliding, sliding, full) x 8, a window of 2,048, 2 leading dense
layers of 6,144, then 128 SwiGLU experts of 1,024 top-8 under a sigmoid
router with balancing biases, beside 1 shared expert), its loss, the
gradients of that loss by ``jax.grad`` and the biases' rule, in float32
``jax.numpy`` with ``default_matmul_precision("highest")``: no kernel, no
grouped product, no cache. Written from the equations below (those of
``transformers``' ``modeling_afmoe.py``), not from the program.

For the stream ``h_t`` in R^D; ``Hq`` query heads, ``Hkv`` key/value
heads, ``g = Hq / Hkv``, head size ``d`` with ``n = d / 2`` pairs; a
matrix maps a row vector; ``h_0 = sqrt(D) * emb[ids]``. Layer ``l`` of
kind ``c``::

    a = RMSNorm(h)
    q = RoPE_c(RMSNorm_head(a Wq))  [Hq, d]     k likewise  [Hkv, d]
    v = a Wv  [Hkv, d]              gate = a Wg  [Hq d]
    o[t, j] = sum_{s: 0 <= t - s < W_c} softmax_s(q[t, j] . k[s, j // g] / sqrt(d)) v[s, j // g]
    h' = h + RMSNorm_post((concat_j(o) * sigmoid(gate)) Wo)

``W_c`` is ``sliding_window`` on a sliding layer and every causal key on
a full one; ``RoPE_c`` turns pair ``i`` (entries ``i`` and ``i + n``:
half-split) of a SLIDING layer's heads by ``t * theta^(-i / n)`` and is
the identity on a FULL layer. Then, in the first ``num_dense_layers``
layers::

    m = RMSNorm(h');  h'' = h' + RMSNorm_post((silu(m Wg) * (m Wu)) Wd)

and in the others, under the layer's balancing biases ``b [E]``::

    m = RMSNorm(h');  s = sigmoid(m Wr) over all E;  E_t = top8(s + b)
    w_e = route_scale * s_e / (sum_{E_t} s + 1e-20)            (without b)
    f = (silu(m Sg) * (m Su)) Sd + sum_{e in E_t, e held} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
    h'' = h' + RMSNorm_post(f)

Final RMSNorm, the untied head over the vocabulary slice, the weighted
mean cross-entropy; no auxiliary loss. After a step the biases move by
``load_balance_coeff`` against the sign of each expert's load (the
(token, choice) pairs it was sent, of all ``E`` experts) over the
layer's mean (``balance_step``). Every held expert is computed for every
token and masked; what the absent experts would add is left out, as the
system leaves it out (the deployment's other chips hold them); the
shared expert is whole.

``choices [L_moe, B, T, 8]`` (``batch["expert_choice"]``), where given,
take the place of ``E_t`` in every expert layer, gates and loads with
them: the comparison under ONE routing.

DEPARTURES from what ``config.json`` has a key for, each written as the
published modelling code computes it (the configuration file's
``assumed`` has each with its reason):

1. ``mup_enabled`` true: the embedding's output is multiplied by
   ``sqrt(hidden_size)`` (the config names the switch, not the factor);
2. the attention's gate ``Wg`` (hidden -> heads x head size, no bias)
   and ``o * sigmoid(gate)`` before ``Wo``;
3. RMSNorm over each head on ``q`` and ``k``;
4. no RoPE at all on a full layer (the config has one ``rope_theta``
   and says nothing of the layer kinds' difference);
5. a norm on each sub-layer's OUTPUT as well as on its input: four
   norms a layer;
6. the biases' rule: ``b -= load_balance_coeff * sign(load - mean)``,
   the auxiliary-loss-free rule under torchtitan's name for that key;
   the deltas are not centred (centring changes no choice);
7. the sliding window as ``transformers`` reads it: ``0 <= t - s <
   sliding_window``, the token itself and the 2,047 before it.

Of FORM, not of arithmetic: each layer's kind reaches the program as
DATA (``layer_tables``: ``rope_w`` and ``W_c`` a layer, and three
switches that are 1 for the model), so that one compiled program serves
the configuration and the control that reads the model as another's
block (no gate, RoPE on the full layers too, a softmax router with
renormalised gates, no shared expert); the expert layers, identical in
shape, run under one ``lax.scan``, each rematerialised; the band is a
``[block, T]`` mask a block of ``Q_BLOCK`` queries (``lax.map``), so
that T = 8192 fits beside the training state; an expert at a time,
rematerialised.
"""

from __future__ import annotations

NEG = -1e30
Q_BLOCK = 256
SLIDING, FULL = "sliding_attention", "full_attention"


def rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def layer_kinds(m: dict) -> list:
    """Each held layer's kind, the dense layers' first: ``layer_types``
    repeated over ``num_layers``."""
    period = list(m["layer_types"])
    assert int(m["num_layers"]) % len(period) == 0, (period, m["num_layers"])
    return period * (int(m["num_layers"]) // len(period))


def layer_tables(m: dict, as_another_models_block: bool = False):
    """What tells a layer its kind, as arrays over ALL the held layers
    (``rope_w [L, n]``, ``window [L]``: ``seq_len`` and more is every
    causal key), and the three switches (1: the model's). With the
    flag, the tables of the negative control: the attention ungated,
    RoPE on the full layers too, a softmax router whose gates sum to
    one, no shared expert."""
    import numpy as np
    n = int(m["head_dim"]) // 2
    plain = float(m["rope_theta"]) ** (-np.arange(n, dtype=np.float64) / n)
    far = 2 ** 30
    rows = []
    for kind in layer_kinds(m):
        assert kind in (SLIDING, FULL), kind
        sliding = kind == SLIDING
        turns = sliding or as_another_models_block
        rows.append((plain if turns else 0.0 * plain,
                     int(m["sliding_window"]) if sliding else far))
    w, window = zip(*rows)
    on = 0.0 if as_another_models_block else 1.0
    return {"rope_w": np.asarray(w, np.float32),
            "window": np.asarray(window, np.int32),
            "attn_gate_on": np.float32(on), "shared_on": np.float32(on),
            "sigmoid_router": np.float32(on)}


def rope(x, w):
    """``x [B, T, H, 2n]``: pair ``i`` (entries ``i``, ``i + n``) turned
    by ``t * w[i]``; zeros turn nothing."""
    import jax.numpy as jnp
    n = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * w
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
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


def swiglu(x, w_gate, w_up, w_down):
    import jax
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def attention(m, p, kind, gate_on, h):
    """``h + RMSNorm_post((o * sigmoid(gate)) Wo)``; ``gate_on`` 0 is
    the control's attention without its gate."""
    import jax
    B, T, D = h.shape
    Hq, Hkv, d = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    a = rms_norm(h, p["ln1"], eps)
    q = rms_norm((a @ p["wq"]).reshape(B, T, Hq, d), p["q_norm"], eps)
    k = rms_norm((a @ p["wk"]).reshape(B, T, Hkv, d), p["k_norm"], eps)
    v = (a @ p["wv"]).reshape(B, T, Hkv, d)
    q, k = rope(q, kind["rope_w"]), rope(k, kind["rope_w"])
    o = banded_attention(q, k, v, kind["window"]).reshape(B, T, Hq * d)
    gate = jax.nn.sigmoid(a @ p["w_attn_gate"])
    o = o * (gate_on * gate + (1.0 - gate_on))
    return h + rms_norm(o @ p["wo"], p["ln1_post"], eps)


def dense_layer(m, p, kind, gate_on, h):
    h = attention(m, p, kind, gate_on, h)
    x = rms_norm(h, p["ln2"], m["rms_norm_eps"])
    return h + rms_norm(swiglu(x, p["w_gate"], p["w_up"], p["w_down"]),
                        p["ln2_post"], m["rms_norm_eps"])


def route(m, x, router, bias, sigmoid_router, forced=None, fed=None):
    """``(chosen [N, k], gates [N, k], the router's own top-k, the
    scores [N, E], the mean of the chosen scores' sum)`` on the
    normalised rows ``x [N, D]``; with the switch at 0 the control's
    router: softmax, no bias, gates that sum to one. ``forced [N, k]``
    takes the place of the router's own top-k (where ``fed``, a traced
    0 / 1, is given: only where it is 1)."""
    import jax
    import jax.numpy as jnp
    kx = int(m["experts_per_token"])
    logits = x @ router
    scores = jnp.where(sigmoid_router > 0, jax.nn.sigmoid(logits),
                       jax.nn.softmax(logits, axis=-1))
    own = jax.lax.top_k(scores + sigmoid_router * bias, kx)[1]
    chosen = own if forced is None else forced
    if forced is not None and fed is not None:
        chosen = jnp.where(fed > 0, forced, own)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    ours = top / (total + 1e-20) if m["route_norm"] else top
    gates = jnp.where(sigmoid_router > 0,
                      float(m["route_scale"]) * ours, top / total)
    return chosen, gates, own, scores, jnp.mean(total)


def expert_mix(m, p, bias, switches, x, forced=None, fed=None):
    """``f [N, D]`` of the normalised rows ``x [N, D]``: the shared
    expert and the chosen experts among those ``p["w_gate"]`` holds
    (from ``first_expert`` on), before the output's norm; and what the
    router did."""
    import jax
    import jax.numpy as jnp
    chosen, gates, own, scores, gate_sum = route(
        m, x, p["router"], bias, switches["sigmoid_router"], forced, fed)
    load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[1]), axis=(0, 1))
    out = switches["shared_on"] * swiglu(
        x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"])
    expert = jax.checkpoint(swiglu)
    for e in range(p["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == m["first_expert"] + e, gates, 0.0),
                       axis=-1)
        out = out + mine[:, None] * expert(x, p["w_gate"][e], p["w_up"][e],
                                           p["w_down"][e])
    return out, {"expert_choice": own, "router_scores": scores,
                 "load": load, "gate_sum_mean": gate_sum}


def expert_layer(m, p, kind, bias, switches, h, forced=None, fed=None):
    """A layer after the dense ones: the new stream and what the router
    did."""
    B, T, D = h.shape
    h = attention(m, p, kind, switches["attn_gate_on"], h)
    x = rms_norm(h, p["ln2"], m["rms_norm_eps"]).reshape(B * T, D)
    out, picked = expert_mix(m, p, bias, switches, x, forced, fed)
    return h + rms_norm(out, p["ln2_post"],
                        m["rms_norm_eps"]).reshape(B, T, D), picked


def _forward(m, params, bias, batch, tables):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    params = jax.tree.map(lambda a: a.astype(f32), params)
    x, y, w = batch["x"], batch["y"], batch["w"]
    B, T = x.shape
    Ld = int(m["num_dense_layers"])
    L = int(m["num_layers"]) - Ld
    forced, fed = batch.get("expert_choice"), batch.get("expert_choice_fed")
    if forced is not None:
        forced = forced.reshape(L, B * T, -1)
    kinds = {k: tables[k] for k in ("rope_w", "window")}
    switches = {k: tables[k] for k in ("attn_gate_on", "shared_on",
                                       "sigmoid_router")}
    h = jnp.sqrt(f32(m["model_dim"])) * jnp.take(params["emb"], x, axis=0)

    for i in range(Ld):
        h = jax.checkpoint(
            lambda p, kind, h: dense_layer(m, p, kind,
                                           switches["attn_gate_on"], h))(
            jax.tree.map(lambda a: a[i], params["dense"]),
            jax.tree.map(lambda a: a[i], kinds), h)

    # the expert layers have the same shapes and their kind is data, so
    # one body under a scan: a quarter of the program to compile, the
    # same arithmetic
    @jax.checkpoint
    def body(h, xs):
        p, kind, bias_l, forced_l = xs
        return expert_layer(m, p, kind, bias_l, switches, h, forced_l, fed)

    h, picked = jax.lax.scan(
        body, h, (params["layers"], jax.tree.map(lambda a: a[Ld:], kinds),
                  bias.astype(f32), forced))
    hidden = rms_norm(h, params["final_norm"], m["rms_norm_eps"])
    logits = hidden.reshape(B * T, -1) @ params["head"]
    real = jnp.arange(logits.shape[1]) < m["vocab_size"]
    logits = jnp.where(real[None, :], logits, -jnp.inf)
    nll = jax.nn.logsumexp(logits, axis=1) \
        - jnp.take_along_axis(logits, y.reshape(-1, 1), axis=1)[:, 0]
    wf = w.reshape(-1)
    loss = jnp.sum(nll * wf) / jnp.sum(wf)
    return loss, {"nll": nll.reshape(B, T), "logits": logits, **picked}


def _fields(model: dict) -> dict:
    m = dict(model)
    m.setdefault("first_expert", 0)
    return m


def _device_tables(tables: dict) -> dict:
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in tables.items()}


def forward(params, bias, batch, model: dict, tables=None):
    """``(loss, outputs)`` of the whole model on ``batch`` (``x``, ``y``,
    ``w`` and optionally ``expert_choice [L_moe, B, T, k]``) under the
    biases ``bias [L_moe, E]``; ``outputs`` holds ``nll [B, T]``,
    ``logits`` and, stacked over the expert layers, ``expert_choice``
    (the router's own top-k, whatever was forced), ``router_scores``
    (without the biases), ``load [L_moe, E]`` and ``gate_sum_mean``.
    ``tables``: ``layer_tables``' (None: the model's own)."""
    import jax
    m = _fields(model)
    tables = _device_tables(layer_tables(m) if tables is None else tables)
    with jax.default_matmul_precision("highest"):
        return _forward(m, params, bias, batch, tables)


def balance_step(bias, load, rate):
    """The biases after a step of loads ``load [L_moe, E]``."""
    import numpy as np
    load = np.asarray(load, np.float64)
    return np.asarray(bias) - rate * np.sign(
        load - load.mean(axis=-1, keepdims=True))


# whose gradients are compared: of the expert layers (stacked over them)
# the queries' projection, the attention's gate, the experts' and the
# shared expert's gate matrices and the router; of the dense layers the
# MLP's up matrix; and the table
GRAD_ARRAYS = ("wq", "w_attn_gate", "w_gate", "shared_w_gate", "router")
DENSE_GRAD_ARRAYS = ("w_up",)
TABLE = "emb"


def compared(params) -> dict:
    """The leaves whose gradients ``loss_and_grads`` returns: ``{name:
    leaf}``, the dense layers' under ``dense/<name>``."""
    sub = {k: params["layers"][k] for k in GRAD_ARRAYS}
    if "dense" in params:
        sub.update({f"dense/{k}": params["dense"][k]
                    for k in DENSE_GRAD_ARRAYS})
    sub[TABLE] = params[TABLE]
    return sub


def with_compared(params, sub: dict) -> dict:
    """``params`` with ``compared``'s leaves replaced by ``sub``'s."""
    out = {**params, TABLE: sub[TABLE],
           "layers": {**params["layers"],
                      **{k: sub[k] for k in GRAD_ARRAYS}}}
    if "dense" in params:
        out["dense"] = {**params["dense"],
                        **{k: sub[f"dense/{k}"] for k in DENSE_GRAD_ARRAYS}}
    return out


def loss_and_grads(params, bias, batch, model: dict, tables=None,
                   programs=None):
    """``(outputs, grads)``: ``forward``'s outputs without the logits,
    and the gradient of the loss with respect to ``compared``'s leaves,
    by ``jax.grad``. A caller that comes again with the same shapes
    passes the same dict as ``programs``: the compiled program is left
    there, and serves any ``tables`` AND a batch with or without a fed
    routing (the program always takes one, and a traced switch says
    whether it counts: one compilation where the two shapes of batch
    would make two of over a minute each)."""
    import json
    import time

    import jax
    import numpy as np
    m = _fields(model)
    tables = _device_tables(layer_tables(m) if tables is None else tables)
    fed = "expert_choice" in batch
    L = int(m["num_layers"]) - int(m["num_dense_layers"])
    batch = {**batch, "expert_choice_fed": np.float32(fed),
             "expert_choice": batch["expert_choice"] if fed else np.zeros(
                 (L, *np.shape(batch["x"]), int(m["experts_per_token"])),
                 np.int32)}

    # the batch, the biases and the tables are arguments, not constants
    # of the program: one compiled program (and one entry of the compile
    # cache) for every seed and for the control
    def loss_of(sub, params, bias, batch, tables):
        return _forward(m, with_compared(params, sub), bias, batch, tables)

    sub = compared(params)
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                          (sub, params, bias, batch, tables))
    key = json.dumps([m, str(shapes)], sort_keys=True, default=str)
    programs = {} if programs is None else programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        if key not in programs:
            programs[key] = jax.jit(jax.grad(loss_of, has_aux=True)).lower(
                sub, params, bias, batch, tables).compile()
        t1 = time.perf_counter()
        grads, outputs = jax.block_until_ready(
            programs[key](sub, params, bias, batch, tables))
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
    k, v, o and gate projections and attention's two products over ITS
    pairs (a sliding layer's band, a full layer's causal triangle,
    averaged over ``seq_len``); a dense layer's MLP; an expert layer's
    router, shared expert and ``experts_per_token * experts_held /
    num_experts`` routed experts a token; and the head over the
    slice."""
    m = model
    D, T = int(m["model_dim"]), int(m["seq_len"])
    Hq, Hkv, Dh = (int(m[k]) for k in ("num_heads", "num_kv_heads",
                                       "head_dim"))
    Ld = int(m["num_dense_layers"])
    proj = 2 * D * (3 * Hq * Dh + 2 * Hkv * Dh)
    dense = 3 * 2 * D * int(m["dense_mlp_dim"])
    F = int(m["expert_dim"])
    sparse = 2 * D * int(m["num_experts"]) \
        + 3 * 2 * D * F * int(m["num_shared_experts"]) \
        + int(m["experts_per_token"]) * int(m["experts_held"]) \
        / int(m["num_experts"]) * 3 * 2 * D * F
    layers = 0.0
    for i, kind in enumerate(layer_kinds(m)):
        window = int(m["sliding_window"]) if kind == SLIDING else T
        attention = 2 * 2 * Hq * Dh * attended_pairs(T, window) / T
        layers += proj + attention + (dense if i < Ld else sparse)
    head = 2 * D * int(m["vocab_size"])
    return int(3 * (layers + head))
