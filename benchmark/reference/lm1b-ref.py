"""The plain reference of ``lm1b-ref``: the LM1B language model's
forward pass and its exact negative log-likelihood, in float32
``jax.numpy`` with ``default_matmul_precision("highest")``: no kernel,
no sampling, no dropout, a Python-level recurrence.

The model (Jozefowicz et al. 2016's LSTM-2048-512; reference
``examples/lm1b/language_model.py``): an embedding row of 512 for each
word, one LSTM layer of 2048 cells with a projection to 512 (LSTMP), a
softmax over the vocabulary from the projected state. As
``models/lm1b.py`` writes the cell: one fused matrix for
``[x_t, h_{t-1}]``, gate order i, f, g, o, forget bias +1,

    c_t = sigmoid(f + 1) * c_{t-1} + sigmoid(i) * tanh(g)
    h_t = (sigmoid(o) * tanh(c_t)) @ w_proj

and the loss of position t is ``logsumexp(h_t W^T + b) - (h_t W^T +
b)[y_t]`` over the real vocabulary (rows added to split the tables
evenly are left out).

Departure from the published description: none in the forward pass.
The system computes in bfloat16 with float32 tables; the reference
computes everything in float32, which is what the tolerances measure.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _nll_and_lstm_grads(vocab: int, hidden_dim: int, proj_dim: int):
    """The jitted forward pass and its gradient, made once a size."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def forward(lstm, params, x, y, w):
        emb = jnp.take(params["emb"].astype(f32), x, axis=0)    # [B, T, E]
        wg = lstm["w"].astype(f32)
        bg = lstm["b"].astype(f32)
        wp = lstm["w_proj"].astype(f32)
        batch_size, steps = x.shape
        c = jnp.zeros((batch_size, hidden_dim), f32)
        h = jnp.zeros((batch_size, proj_dim), f32)
        outs = []
        for t in range(steps):
            gates = jnp.concatenate([emb[:, t], h], axis=-1) @ wg + bg
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c \
                + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = (jax.nn.sigmoid(o) * jnp.tanh(c)) @ wp
            outs.append(h)
        hs = jnp.stack(outs, axis=1).reshape(batch_size * steps, proj_dim)
        logits = hs @ params["softmax_w"].astype(f32).T \
            + params["softmax_b"].astype(f32)[:, 0][None, :]
        real = jnp.arange(logits.shape[1]) < vocab
        logits = jnp.where(real[None, :], logits, -jnp.inf)
        labels = y.reshape(-1)
        nll = jax.nn.logsumexp(logits, axis=1) \
            - jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
        wf = w.reshape(-1)
        return jnp.sum(nll * wf) / jnp.sum(wf), nll.reshape(x.shape)

    return jax.jit(jax.grad(forward, has_aux=True))


def nll_and_lstm_grads(params, batch, model: dict):
    """``(nll, grads)``: the negative log-likelihood of every position,
    ``[sequences, steps]``, and the gradient of their weighted mean
    (weights ``batch["w"]``) with respect to the LSTM's three arrays
    (``w``, ``b``, ``w_proj``), by ``jax.grad`` of the forward pass."""
    import jax
    import numpy as np

    run = _nll_and_lstm_grads(int(model["vocab_size"]),
                              int(model["hidden_dim"]),
                              int(model["proj_dim"]))
    with jax.default_matmul_precision("highest"):
        grads, nll = run(params["lstm"], params, batch["x"], batch["y"],
                         batch["w"])
    return np.asarray(nll), {k: np.asarray(v) for k, v in grads.items()}


def train_matmul_flops_per_token(model: dict) -> int:
    """Matrix-product operations the forward and backward passes need
    for one predicted word (``common/flops.lm1b_matmul_flops_per_word``'s
    count, copied): forward the fused gate product ``[1, E+P] x [E+P,
    4H]``, the projection ``[1, H] x [H, P]`` and the sampled logits
    ``[1, P] x [P, S+1]``; backward twice that. Recomputed work and
    element-wise work do not count."""
    E, H, P = (int(model[k]) for k in ("emb_dim", "hidden_dim", "proj_dim"))
    forward = 2 * (E + P) * 4 * H + 2 * H * P \
        + 2 * P * (int(model["num_samples"]) + 1)
    return 3 * forward
