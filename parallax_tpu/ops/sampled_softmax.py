"""Sampled softmax over a row-sharded vocabulary.

The reference's LM1B model trains a 793k-word softmax with TF's sampled
softmax and a log-uniform (Zipfian) candidate sampler, with the softmax
weight/bias variables partitioned across parameter servers
(reference: examples/lm1b/language_model.py:33-45, :60-75).

TPU-native version: the softmax weight matrix and bias live row-sharded
over the 'shard' mesh axis and are touched *only* via
`ops.embedding_lookup` gathers (labels + sampled candidates), so the
classifier routes them through the sparse path — only the gathered rows
ever cross ICI, never the [V, D] matrix, matching the reference's PS pull
of sampled rows.

All shapes are static (num_samples fixed) and sampling uses the in-step
PRNG — no host round trip, no dynamic shapes under jit.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from parallax_tpu.ops import embedding as emb_ops


def log_uniform_candidates(rng: jax.Array, num_samples: int,
                           vocab_size: int) -> jax.Array:
    """Sample ids from the log-uniform (Zipf) distribution
    P(k) = log((k+2)/(k+1)) / log(V+1), matching TF's
    LogUniformCandidateSampler used by the reference LM1B model.

    Inverse-CDF: k = floor(exp(u * log(V+1))) - 1.
    """
    u = jax.random.uniform(rng, (num_samples,))
    k = jnp.exp(u * jnp.log(float(vocab_size + 1))) - 1.0
    return jnp.clip(k.astype(jnp.int32), 0, vocab_size - 1)


def log_uniform_prob(ids: jax.Array, vocab_size: int) -> jax.Array:
    ids_f = ids.astype(jnp.float32)
    return (jnp.log((ids_f + 2.0) / (ids_f + 1.0))
            / jnp.log(float(vocab_size + 1)))


def _mxu_matmul(a: jax.Array, bt: jax.Array,
                dtype: Optional[jnp.dtype]) -> jax.Array:
    """``a @ bt.T`` with inputs cast to ``dtype`` (bf16: native MXU
    rate) and float32 accumulation; ``dtype=None`` keeps the operands'
    own precision (fp32 matmuls run at a fraction of MXU throughput)."""
    if dtype is not None:
        a, bt = a.astype(dtype), bt.astype(dtype)
    return jnp.matmul(a, bt.T, preferred_element_type=jnp.float32)


def sampled_softmax_loss(
    softmax_w: jax.Array,          # [V_padded, D] (row-sharded or not)
    softmax_b: jax.Array,          # [V_padded, 1] (column vector so the
                                   #   bias is itself a gather-only,
                                   #   row-shardable table)
    hidden: jax.Array,             # [N, D]
    labels: jax.Array,             # [N] int32
    rng: jax.Array,
    num_samples: int,
    vocab_size: int,
    remove_accidental_hits: bool = True,
    matmul_dtype: Optional[jnp.dtype] = jnp.bfloat16,
) -> jax.Array:
    """Per-example sampled-softmax cross-entropy, [N].

    One fused gather serves the label rows and the shared candidate rows
    (ids concatenated), so the sharded-embedding path pays a single
    collective round per step for the whole softmax. The logits matmul
    runs with ``matmul_dtype`` inputs and float32 accumulation (softmax
    corrections, logsumexp and the loss stay float32 throughout).
    """
    # the layer's name on every op it emits, forward and backward
    # (obs/xprof.LAYER_SCOPES); the candidate rows' lookups name
    # themselves "embedding" inside it
    with jax.named_scope("sampled_softmax"):
        return _loss(softmax_w, softmax_b, hidden, labels, rng,
                     num_samples, vocab_size, remove_accidental_hits,
                     matmul_dtype)


def _loss(softmax_w, softmax_b, hidden, labels, rng, num_samples,
          vocab_size, remove_accidental_hits, matmul_dtype):
    n = hidden.shape[0]
    samples = log_uniform_candidates(rng, num_samples, vocab_size)

    ids_all = jnp.concatenate([labels, samples])
    rows = emb_ops.embedding_lookup(softmax_w, ids_all)
    bias = emb_ops.embedding_lookup(softmax_b, ids_all)[:, 0]
    w_true, w_samp = rows[:n], rows[n:]
    b_true, b_samp = bias[:n], bias[n:]

    # Sampled-softmax correction: subtract log(expected count) so the
    # sampled logits are an unbiased estimate of the full softmax.
    logq_true = jnp.log(
        jnp.float32(num_samples)) + jnp.log(
        log_uniform_prob(labels, vocab_size))
    logq_samp = jnp.log(
        jnp.float32(num_samples)) + jnp.log(
        log_uniform_prob(samples, vocab_size))

    ht = hidden if matmul_dtype is None else hidden.astype(matmul_dtype)
    wt = w_true if matmul_dtype is None else w_true.astype(matmul_dtype)
    logits_true = (jnp.einsum("nd,nd->n", ht, wt,
                              preferred_element_type=jnp.float32)
                   + b_true - logq_true)                           # [N]
    logits_samp = (_mxu_matmul(hidden, w_samp, matmul_dtype)
                   + b_samp[None, :] - logq_samp[None, :])         # [N, S]

    if remove_accidental_hits:
        hit = samples[None, :] == labels[:, None]                  # [N, S]
        logits_samp = jnp.where(hit, -1e9, logits_samp)

    logits = jnp.concatenate([logits_true[:, None], logits_samp], axis=1)
    # True class is column 0.
    return (jax.nn.logsumexp(logits, axis=1) - logits[:, 0])


def full_softmax_loss(softmax_w, softmax_b, hidden, labels,
                      vocab_size: Optional[int] = None,
                      matmul_dtype: Optional[jnp.dtype] = None
                      ) -> jax.Array:
    """Full-vocabulary softmax loss (eval path; reference lm1b_eval.py).
    ``softmax_b`` is the [V, 1] column vector used by the train path.

    The default computes exact fp32 logits — this is the eval/parity
    path, and its perplexities must stay reference-comparable without
    callers knowing about dtypes. Pass ``matmul_dtype=jnp.bfloat16`` to
    opt into the MXU-native bf16-in/fp32-accumulate matmul (what the
    lm1b train-baseline model does via its compute dtype)."""
    logits = (_mxu_matmul(hidden, softmax_w, matmul_dtype)
              + softmax_b[:, 0][None, :])
    if vocab_size is not None:
        logits = emb_ops.mask_padded_logits(logits, vocab_size)
    lse = jax.nn.logsumexp(logits, axis=1)
    true_logit = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return lse - true_logit
