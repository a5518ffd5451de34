"""LM1B language model — the flagship sparse/hybrid workload.

Re-expression of the reference's LM1B example
(reference: examples/lm1b/language_model.py and language_model_graph.py):
a single-layer LSTM with projection over a 793,470-word vocabulary,
log-uniform sampled softmax (num_samples=8192), embedding and softmax
variables partitioned across the sparse path
(language_model.py:33-45 uses parallax.get_partitioner for both).

TPU-native design decisions:
  * the recurrence is a `lax.scan` over time — static shapes, one fused
    [B, E+P] x [E+P, 4H] matmul per step on the MXU;
  * embedding + softmax weight + softmax bias are gather-only tables ->
    the trace-time classifier routes all three to the row-sharded path;
    vocab is padded so rows split evenly for any divisor of the device
    count (partition auto-search reshards without shape changes);
  * sampled softmax is one fused gather for labels+candidates (see
    ops/sampled_softmax.py);
  * compute runs in bfloat16 (MXU native), params/optimizer in float32.

Batch contract matches the reference driver
(examples/lm1b/lm1b_distributed_driver.py:84-96): feeds "x" [B, T] int32,
"y" [B, T] int32, "w" [B, T] float weights; metric words/sec derives
from sum(w) per step.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from parallax_tpu.core.engine import Model
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import sampled_softmax as ss_ops


@dataclasses.dataclass
class LM1BConfig:
    vocab_size: int = 793470          # reference lm1b vocabulary
    emb_dim: int = 512
    hidden_dim: int = 2048
    proj_dim: int = 512
    num_samples: int = 8192
    keep_prob: float = 0.9            # reference language_model.py dropout
    max_grad_norm: float = 10.0
    learning_rate: float = 0.2
    num_partitions: Optional[int] = None  # None -> pad for device count
    compute_dtype: jnp.dtype = jnp.bfloat16
    # dtype of the big gather-only tables (emb/softmax_w/softmax_b) and
    # therefore of every row plane the sparse path puts on the wire —
    # bf16 halves the dominant wire term (and the slice-adagrad
    # accumulators; the LSTM stack and its optimizer stay fp32).
    table_dtype: jnp.dtype = jnp.float32
    # Scatter-only adagrad over touched table rows (reference
    # SparseApplyAdagrad, graph_transform_lib.py:71-77). Must bound the
    # distinct rows a step touches on emb (batch·num_steps ids) and
    # softmax_w (num_samples + batch·num_steps labels); None = dense
    # adagrad updates.
    max_touched_rows: Optional[int] = None
    # "slices": table grads stay (ids, rows) pairs end-to-end — the
    # reference's exact gradient processing (IndexedSlices straight into
    # the sparse Adagrad kernel, with the global-norm clip covering ONLY
    # the LSTM variables: language_model_graph.py:42-58) and the fast
    # path on TPU (no dense [V, D] cotangent or table-grad norm).
    # Requires Config(sparse_grad_mode="slices"). "dense": all grads
    # dense, clip covers every variable (round-1 behavior).
    sparse_grad_mode: str = "dense"
    # lax.scan unroll factor for the LSTM time loop: >1 trades compiled
    # code size for fewer loop iterations (amortizes the per-iteration
    # loop overhead that dominates small-batch recurrent steps on TPU).
    # T % unroll need not hold (lax.scan handles remainders).
    lstm_scan_unroll: int = 1
    # 'pallas': run the recurrence as the VMEM-resident kernel
    # (ops/pallas_lstm.py) — weights fetched once per batch tile
    # instead of once per time step (~T-fold HBM-traffic cut on the
    # scan's dominant term), forward AND backward: the time-reversed
    # backward kernel consumes saved residuals (gate activations + c
    # trajectory) with fp32 (dc, dh) carries, so training neither
    # recomputes the forward nor re-fetches weights per step. Off-TPU
    # (and on VMEM-unfittable sizes) the backward drops to the XLA
    # residual-scan executor; PARALLAX_LSTM_BWD overrides
    # (auto|kernel|scan|recompute). 'xla' (default): lax.scan.
    lstm_impl: str = "xla"

    @property
    def padded_vocab(self) -> int:
        return emb_ops.padded_vocab_for(self.vocab_size,
                                        self.num_partitions)


def tiny_config(**kw) -> LM1BConfig:
    """Small config for tests / dry runs."""
    defaults = dict(vocab_size=1000, emb_dim=32, hidden_dim=64,
                    proj_dim=32, num_samples=64, keep_prob=1.0,
                    learning_rate=0.1)
    defaults.update(kw)
    return LM1BConfig(**defaults)


def build_model(cfg: LM1BConfig, full_softmax: bool = False) -> Model:
    """``full_softmax=True`` builds the naive dense baseline (loss over the
    whole vocab, softmax matrix used densely -> classified dense and
    replicated) — the "stock TF" path the reference benches against."""
    V = cfg.padded_vocab
    E, H, P = cfg.emb_dim, cfg.hidden_dim, cfg.proj_dim

    def init_fn(rng):
        ks = jax.random.split(rng, 6)
        u = lambda k, shape, s: jax.random.uniform(k, shape, jnp.float32,
                                                   -s, s)
        scale = 1.0 / np.sqrt(E)
        td = cfg.table_dtype
        return {
            "emb": u(ks[0], (V, E), scale).astype(td),
            "lstm": {
                # one fused kernel for [x, h_proj] -> gates
                "w": u(ks[1], (E + P, 4 * H), 1.0 / np.sqrt(E + P)),
                "b": jnp.zeros((4 * H,), jnp.float32),
                "w_proj": u(ks[2], (H, P), 1.0 / np.sqrt(H)),
            },
            "softmax_w": u(ks[3], (V, P), 1.0 / np.sqrt(P)).astype(td),
            "softmax_b": jnp.zeros((V, 1), td),
        }

    def lstm_scan(lstm, x_seq):
        """x_seq: [T, B, E] time-major. Returns [T, B, P] projections."""
        B = x_seq.shape[1]
        w = lstm["w"].astype(cfg.compute_dtype)
        b = lstm["b"].astype(cfg.compute_dtype)
        w_proj = lstm["w_proj"].astype(cfg.compute_dtype)
        if cfg.lstm_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown lstm_impl {cfg.lstm_impl!r}; "
                f"expected 'xla' or 'pallas'")
        if cfg.lstm_impl == "pallas":
            # NOTE: the kernel carries (c, h) in fp32 (strictly more
            # precise than this scan's compute-dtype carries); under
            # fp32 compute the two paths are numerically identical
            from parallax_tpu.core.mesh import BATCH_AXES
            from parallax_tpu.ops import pallas_lstm
            mesh = emb_ops.current_mesh()
            return pallas_lstm.lstm_scan(
                x_seq.astype(cfg.compute_dtype), w, b, w_proj,
                impl="pallas", mesh=mesh,
                batch_axes=(BATCH_AXES if mesh is not None else None))

        def cell(carry, x_t):
            c, h = carry
            zx = jnp.concatenate([x_t, h], axis=-1)
            gates = zx @ w + b
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h_full = jax.nn.sigmoid(o) * jnp.tanh(c)
            h = h_full @ w_proj
            return (c, h), h

        c0 = jnp.zeros((B, H), cfg.compute_dtype)
        h0 = jnp.zeros((B, P), cfg.compute_dtype)
        # the same layer name the pallas path carries
        # (obs/xprof.LAYER_SCOPES)
        with jax.named_scope("lstm"):
            (_, _), hs = jax.lax.scan(cell, (c0, h0), x_seq,
                                      unroll=max(1, cfg.lstm_scan_unroll))
        return hs

    def loss_fn(params, batch, rng):
        x, y = batch["x"], batch["y"]
        w = batch.get("w")
        if w is None:
            w = jnp.ones(x.shape, jnp.float32)
        B, T = x.shape
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        drop_rng, samp_rng = jax.random.split(rng)

        emb = emb_ops.embedding_lookup(params["emb"], x)       # [B, T, E]
        emb = emb.astype(cfg.compute_dtype)
        in_rng, out_rng = jax.random.split(drop_rng)
        if cfg.keep_prob < 1.0:
            mask = jax.random.bernoulli(in_rng, cfg.keep_prob, emb.shape)
            emb = jnp.where(mask, emb / cfg.keep_prob, 0.0)

        hs = lstm_scan(params["lstm"], jnp.swapaxes(emb, 0, 1))  # [T, B, P]
        if cfg.keep_prob < 1.0:
            # LSTM-output dropout (reference language_model.py applies
            # DropoutWrapper output dropout per step; independent masks
            # per (t, b) position are equivalent).
            mask = jax.random.bernoulli(out_rng, cfg.keep_prob, hs.shape)
            hs = jnp.where(mask, hs / cfg.keep_prob, 0.0)
        hidden = jnp.swapaxes(hs, 0, 1).reshape(B * T, P)
        hidden = hidden.astype(jnp.float32)

        labels = y.reshape(B * T)
        if full_softmax:
            # train-baseline semantics: the model's compute dtype governs
            # the logits matmul (bf16 by default — explicit opt-in; the
            # op itself defaults to fp32 for eval parity)
            mm = (None if cfg.compute_dtype == jnp.float32
                  else cfg.compute_dtype)
            losses = ss_ops.full_softmax_loss(
                params["softmax_w"], params["softmax_b"], hidden, labels,
                cfg.vocab_size, matmul_dtype=mm)                # [B*T]
        else:
            losses = ss_ops.sampled_softmax_loss(
                params["softmax_w"], params["softmax_b"], hidden, labels,
                samp_rng, cfg.num_samples, cfg.vocab_size)      # [B*T]
        wf = w.reshape(B * T)
        total_w = jnp.maximum(jnp.sum(wf), 1e-8)
        loss = jnp.sum(losses * wf) / total_w
        return loss, {"words": jnp.sum(wf)}

    if cfg.sparse_grad_mode == "slices" and not full_softmax:
        # Reference-exact grouping (language_model_graph.py:42-58): the
        # engine masks the slice tables out of `tx`, so the global-norm
        # clip sees exactly the LSTM group; table slices go straight to
        # scatter-only adagrad, unclipped.
        from parallax_tpu.ops.sparse_optim import SliceAdagrad
        tx = optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.adagrad(cfg.learning_rate,
                          initial_accumulator_value=1.0))
        sl = SliceAdagrad(cfg.learning_rate,
                          initial_accumulator_value=1.0)
        return _pin_lstm_replicated(
            Model(init_fn, loss_fn, optimizer=tx,
                  slice_updaters={"emb": sl, "softmax_w": sl,
                                  "softmax_b": sl}))
    if cfg.max_touched_rows and not full_softmax:
        # full_softmax grads touch every softmax_w row, so the touched-
        # rows bound cannot hold there — dense adagrad in that mode.
        from parallax_tpu.ops.sparse_optim import row_sparse_adagrad
        # clip sees the full grads (norm unchanged), then tables take
        # the scatter-only path — trajectory identical to dense adagrad
        tables = {"emb": "table", "softmax_w": "table"}
        tx = optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.multi_transform(
                {"table": row_sparse_adagrad(
                    cfg.learning_rate, cfg.max_touched_rows,
                    initial_accumulator_value=1.0),
                 "rest": optax.adagrad(cfg.learning_rate,
                                       initial_accumulator_value=1.0)},
                param_labels=lambda params: {
                    k: tables.get(k, "rest") for k in params}))
    else:
        tx = optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.adagrad(cfg.learning_rate,
                          initial_accumulator_value=1.0))
    return _pin_lstm_replicated(Model(init_fn, loss_fn, optimizer=tx))


def _pin_lstm_replicated(model: Model) -> Model:
    """Pin the LSTM cell weights replicated in every plan.

    They are consumed on their CONTRACTED dim inside the scan, so
    ZeRO-style row-sharding them (run_option=SHARD, or HYBRID with
    replicate_variables=False) forces the scan backward to reshard the
    saved residuals batch->feature inside the transposed while loop —
    which GSPMD can only do as an involuntary full rematerialization
    (caught by the tuner-plan remat gate, __graft_entry__ phase 6).
    Sharded storage of [E+P, 4H] + bias + projection buys ~nothing;
    the tables and softmax still shard under every run option."""
    from jax.sharding import PartitionSpec as P
    model.param_specs.setdefault("lstm/*", P())
    return model


def make_batch(rng: np.random.Generator, batch_size: int, num_steps: int,
               vocab_size: int):
    """Synthetic Zipf-ish batch with the reference driver's feed keys."""
    x = (rng.zipf(1.3, size=(batch_size, num_steps)) - 1) % vocab_size
    y = np.roll(x, -1, axis=1)
    return {"x": x.astype(np.int32), "y": y.astype(np.int32),
            "w": np.ones((batch_size, num_steps), np.float32)}


# ----- serving decode ------------------------------------------------------
# Incremental decode for serve/adapters.LM1BDecodeProgram: the cache is
# the LSTM carry itself ([S, H] cell + [S, P] projected hidden per
# slot), not a KV buffer — the adapter that proves the DecodeProgram
# contract isn't transformer-shaped. Greedy decode uses the FULL
# softmax projection (the sampled softmax is a training-only loss).


def _lstm_serve_weights(cfg: LM1BConfig, params):
    cdt = cfg.compute_dtype
    lstm = params["lstm"]
    return (lstm["w"].astype(cdt), lstm["b"].astype(cdt),
            lstm["w_proj"].astype(cdt))


def _lstm_prefill(cfg: LM1BConfig, params, ids, pad_id=0):
    """Run the recurrence over the prompt EXCEPT its last token — the
    first decode step consumes that one (double-stepping it is the
    classic off-by-one). ``ids`` [1, Ts] padded with ``pad_id``; a
    gated scan (valid = j < t0 - 1) leaves the carry untouched on
    padded rows. Returns (c [1, H], h [1, P], base [1], first [1])."""
    cdt = cfg.compute_dtype
    w, b, w_proj = _lstm_serve_weights(cfg, params)
    B, Ts = ids.shape
    emb = emb_ops.embedding_lookup(params["emb"], ids).astype(cdt)
    t0 = jnp.sum((ids[0] != pad_id).astype(jnp.int32))
    c0 = jnp.zeros((B, cfg.hidden_dim), cdt)
    h0 = jnp.zeros((B, cfg.proj_dim), cdt)

    def cell(carry, inp):
        c, h = carry
        x_t, valid = inp
        zx = jnp.concatenate([x_t, h], axis=-1)
        gates = zx @ w + b
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c2 = (jax.nn.sigmoid(f + 1.0) * c
              + jax.nn.sigmoid(i) * jnp.tanh(g))
        h2 = (jax.nn.sigmoid(o) * jnp.tanh(c2)) @ w_proj
        return (jnp.where(valid, c2, c), jnp.where(valid, h2, h)), None

    valid = jnp.arange(Ts) < (t0 - 1)
    (c, h), _ = jax.lax.scan(cell, (c0, h0),
                             (jnp.swapaxes(emb, 0, 1), valid))
    base = (t0 - 1).astype(jnp.int32)
    first = jnp.take(ids[0], base, mode="clip").astype(jnp.int32)
    return c, h, base[None], first[None]


def _lstm_decode_step(cfg: LM1BConfig, params, tok, c, h):
    """One batched greedy-decode step: ``tok`` [S] is each slot's
    current token; returns (logits [S, padded_vocab] f32, c, h). Every
    op is row-wise, so co-batched slots decode independently."""
    cdt = cfg.compute_dtype
    w, b, w_proj = _lstm_serve_weights(cfg, params)
    x = emb_ops.embedding_lookup(params["emb"], tok).astype(cdt)
    zx = jnp.concatenate([x, h], axis=-1)
    gates = zx @ w + b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = (jax.nn.sigmoid(o) * jnp.tanh(c)) @ w_proj
    logits = (h.astype(jnp.float32)
              @ params["softmax_w"].astype(jnp.float32).T
              + params["softmax_b"].astype(jnp.float32)[:, 0][None, :])
    return emb_ops.mask_padded_logits(logits, cfg.vocab_size), c, h
