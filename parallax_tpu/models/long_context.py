"""Long-context causal transformer LM — sequence-parallel training.

A new first-class capability over the reference (SURVEY.md §5.7: the
reference has no sequence parallelism): the sequence dimension of every
activation is sharded over the mesh's 'shard' axis and attention runs as
ring attention over the ICI ring (ops/ring_attention.py), so the model
trains on sequences far longer than one device's memory would allow. The
batch dimension remains data-parallel over 'repl' — a dp x sp mesh in the
engine's existing two axes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from parallax_tpu.core.engine import Model
from parallax_tpu.core.mesh import (AXIS_PIPE, AXIS_REPL, AXIS_SHARD,
                                    pipeline_stage_count)
from parallax_tpu.ops import embedding as emb_ops
from parallax_tpu.ops import tensor_parallel as tp_ops
from parallax_tpu.ops.ring_attention import (full_attention_reference,
                                             inverse_zigzag_permutation,
                                             ring_attention,
                                             zigzag_permutation)


@dataclasses.dataclass
class LongContextConfig:
    vocab_size: int = 32000
    model_dim: int = 512
    num_heads: int = 8
    mlp_dim: int = 2048
    num_layers: int = 6
    max_len: int = 32768
    learning_rate: float = 3e-4
    # 'ring'    : sequence parallelism — seq over 'shard', ring attention
    # 'tensor'  : tensor parallelism — Megatron column/row-parallel
    #             kernels over 'shard' (ops/tensor_parallel.py; GSPMD
    #             inserts the psum after each row-parallel matmul),
    #             batch data-parallel over 'repl'
    # 'pipeline': pipeline parallelism — layer stages over the mesh's
    #             pipeline axis ('pipe' on a 3-axis (dp, tp, pp) mesh,
    #             else 'shard'), GPipe microbatch pipelining
    #             (ops/pipeline.py), batch data-parallel over 'repl'
    # 'data'    : pure data parallelism (attention unsharded)
    parallelism: str = "ring"
    num_microbatches: int = 4  # pipeline mode
    # pipeline mode schedule:
    # 'gpipe': forward-only scan, AD transposes the backward; stores
    #          O(M) microbatch activations per stage.
    # '1f1b' : fused fwd+bwd 1F1B (ops/pipeline.pipeline_value_and_grad)
    #          via Model.value_and_grad_fn; O(min(M, 2S-1)) activations,
    #          one recompute forward per microbatch.
    pipeline_schedule: str = "gpipe"
    # Interleaved (virtual-stage) scheduling: each device holds
    # virtual_stages non-adjacent layer chunks, cutting the pipeline
    # bubble virtual_stages-fold (ops/pipeline.py). Because the chunk
    # assignment depends on the stage count, virtual_stages > 1 requires
    # declaring ``pipeline_stages`` (the pipeline mesh axis size the
    # model will run on); layers are then STORED in device-major stage
    # order at init so no in-graph cross-shard permute is ever needed.
    virtual_stages: int = 1
    pipeline_stages: Optional[int] = None
    # Megatron sequence parallelism composed with TP (tensor mode only):
    # between-block activations rest sequence-sharded over the same
    # 'shard' axis — the closing all-reduce of each block becomes a
    # reduce-scatter and the entry matmuls re-gather, so norms/residuals
    # hold T/tp tokens per device (ops/tensor_parallel.py docstring).
    tp_sequence_parallel: bool = False
    # zig-zag sequence placement in ring mode: balances the causal
    # workload across the ring (each device holds a low block and its
    # mirrored high block; ops/ring_attention.py computes maskless
    # half-tiles for foreign blocks — about half the tiles on the worst
    # device at large rings, by count). The permute happens in-graph, so
    # feeds stay natural-order. None (default) = AUTO: zigzag whenever
    # the sequence length divides 2*ring (its only extra requirement),
    # contiguous otherwise; True/False forces.
    zigzag: Optional[bool] = None
    # fuse attention with the Pallas flash kernel (data/tensor modes;
    # ring mode has its own collective-fused path)
    use_pallas_attention: bool = False
    # rematerialize each transformer block in the backward pass
    # (jax.checkpoint): activation memory drops from O(layers) to O(1)
    # blocks at ~1/3 extra FLOPs — the standard long-context trade on
    # HBM-bound TPUs. Applies to the data/ring/tensor paths (pipeline
    # schedules own their memory strategy: 1F1B already rematerializes).
    remat: bool = False
    compute_dtype: jnp.dtype = jnp.bfloat16

    @property
    def use_ring_attention(self) -> bool:
        return self.parallelism == "ring"


def tiny_config(**kw) -> LongContextConfig:
    defaults = dict(vocab_size=512, model_dim=32, num_heads=2, mlp_dim=64,
                    num_layers=2, max_len=64)
    if "use_ring_attention" in kw:  # back-compat alias
        kw["parallelism"] = ("ring" if kw.pop("use_ring_attention")
                             else "data")
    defaults.update(kw)
    return LongContextConfig(**defaults)


def build_model(cfg: LongContextConfig) -> Model:
    V, D, Hn = cfg.vocab_size, cfg.model_dim, cfg.num_heads
    dt = cfg.compute_dtype

    if cfg.zigzag and cfg.parallelism != "ring":
        raise ValueError(
            "zigzag placement only applies to parallelism='ring'")
    if cfg.tp_sequence_parallel and cfg.parallelism != "tensor":
        raise ValueError(
            "tp_sequence_parallel only applies to parallelism='tensor'")
    if cfg.parallelism == "tensor" and cfg.use_pallas_attention:
        raise ValueError(
            "parallelism='tensor' uses the XLA attention core (the "
            "Pallas kernel does not partition under GSPMD); unset "
            "use_pallas_attention")
    Vp = int(cfg.virtual_stages)
    if Vp > 1:
        if cfg.parallelism != "pipeline":
            raise ValueError(
                "virtual_stages > 1 only applies to "
                "parallelism='pipeline'")
        if not cfg.pipeline_stages:
            raise ValueError(
                "virtual_stages > 1 requires pipeline_stages (the "
                "'shard' mesh axis size) so the device-major layer "
                "order is fixed at init")
        if cfg.num_layers % (cfg.pipeline_stages * Vp):
            raise ValueError(
                f"num_layers ({cfg.num_layers}) must divide into "
                f"pipeline_stages*virtual_stages = "
                f"{cfg.pipeline_stages}*{Vp}")

    def _layer_storage_order():
        """Original layer index stored at each row of blocks_stacked.

        Identity for V=1; for interleaving, rows follow the device-major
        stage order (ops/pipeline.stage_order_permutation) with each
        stage's layers contiguous."""
        L = cfg.num_layers
        if Vp == 1:
            return list(range(L))
        from parallax_tpu.ops.pipeline import stage_order_permutation
        S = cfg.pipeline_stages
        pc = L // (S * Vp)
        return [g * pc + j
                for g in stage_order_permutation(S, Vp)
                for j in range(pc)]

    def _zigzag_active(mesh, T: int) -> bool:
        if (cfg.parallelism != "ring" or mesh is None
                or mesh.shape[AXIS_SHARD] <= 1):
            return False
        fits = T % (2 * mesh.shape[AXIS_SHARD]) == 0
        if cfg.zigzag is None:
            return fits
        if cfg.zigzag and not fits:
            raise ValueError(
                f"zigzag placement needs sequence length divisible by "
                f"2*ring={2 * mesh.shape[AXIS_SHARD]}; got T={T} "
                f"(set zigzag=None for auto fallback)")
        return cfg.zigzag

    def dense_init(rng, shape):
        return jax.random.normal(rng, shape) * (1.0 / np.sqrt(shape[0]))

    def init_fn(rng):
        ks = jax.random.split(rng, 3 + cfg.num_layers)
        blocks = []
        for i in range(cfg.num_layers):
            bk = jax.random.split(ks[2 + i], 6)
            blocks.append({
                "wqkv": dense_init(bk[0], (D, 3 * D)),
                "wo": dense_init(bk[1], (D, D)),
                "w1": dense_init(bk[2], (D, cfg.mlp_dim)),
                "w2": dense_init(bk[3], (cfg.mlp_dim, D)),
                "ln1": {"s": jnp.ones((D,)), "b": jnp.zeros((D,))},
                "ln2": {"s": jnp.ones((D,)), "b": jnp.zeros((D,))},
            })
        params = {
            "emb": jax.random.normal(ks[0], (V, D)) * 0.02,
            "pos": jax.random.normal(ks[-1], (cfg.max_len, D)) * 0.02,
            "out_w": dense_init(ks[1], (D, V)),
        }
        if cfg.parallelism == "pipeline":
            # stacked layout [L, ...] so layer stages shard over
            # 'shard'; rows in storage order (device-major when
            # interleaving — a one-time permute here instead of a
            # per-step cross-shard gather)
            order = _layer_storage_order()
            params["blocks_stacked"] = jax.tree.map(
                lambda *leaves: jnp.stack([leaves[i] for i in order]),
                *blocks)
        else:
            params["blocks"] = blocks
        return params

    def _stage_pipeline(stacked, n_stages):
        """Validate the stage split and return (staged, stage_fn):
        leaves reshaped [S*V, per_stage, ...] plus the per-stage apply
        (shared by the GPipe loss path and the 1F1B fused path)."""
        if Vp > 1 and n_stages != cfg.pipeline_stages:
            raise ValueError(
                f"model was built for pipeline_stages="
                f"{cfg.pipeline_stages} but the mesh pipeline axis is "
                f"{n_stages}")
        if cfg.num_layers % (n_stages * Vp):
            raise ValueError(
                f"pipeline parallelism needs num_layers "
                f"({cfg.num_layers}) divisible by the "
                f"{n_stages}-stage pipeline axis (x{Vp} virtual)")
        per_stage = cfg.num_layers // (n_stages * Vp)

        def stage_fn(stage_params, x):
            # stage_params leaves: [per_stage, ...]
            for j in range(per_stage):
                x = block_apply(
                    jax.tree.map(lambda p: p[j], stage_params), x)
            return x

        staged = jax.tree.map(
            lambda p: p.reshape((n_stages * Vp, per_stage)
                                + p.shape[1:]), stacked)
        return staged, stage_fn

    def layer_norm(x, s, b):
        m = jnp.mean(x, -1, keepdims=True)
        v = jnp.var(x, -1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-6) * s + b

    tp_mode = cfg.parallelism == "tensor"
    tp_sp = tp_mode and cfg.tp_sequence_parallel

    def attention(x, p):
        B, T, _ = x.shape
        if tp_mode:
            # Megatron column-parallel qkv: each device computes its
            # H/tp heads' projections and runs the attention core
            # locally; the constraints pin the head sharding so GSPMD
            # never gathers the scores.
            qkv = tp_ops.column_parallel(x, p["wqkv"].astype(dt))
        else:
            qkv = x @ p["wqkv"].astype(dt)
        q, k, v = jnp.split(qkv, 3, -1)
        q = q.reshape(B, T, Hn, D // Hn)
        k = k.reshape(B, T, Hn, D // Hn)
        v = v.reshape(B, T, Hn, D // Hn)
        mesh = emb_ops.current_mesh()
        if tp_mode:
            # indivisible head counts fall back to a replicated core —
            # pinning them would pad the H axis and pay involuntary
            # full remat on every backward transpose (see
            # tensor_parallel.heads_shardable)
            h_ax = (AXIS_SHARD if tp_ops.heads_shardable(Hn)
                    else None)
            head = P(AXIS_REPL, None, h_ax, None)
            q = tp_ops.constrain(q, head)
            k = tp_ops.constrain(k, head)
            v = tp_ops.constrain(v, head)
        if cfg.use_ring_attention and mesh is not None:
            placement = ("zigzag" if _zigzag_active(mesh, T)
                         else "contiguous")
            # block_impl 'auto' = flash kernels on TPU; forcing
            # use_pallas_attention makes CPU runs exercise them too
            out = ring_attention(q, k, v, mesh, AXIS_SHARD,
                                 causal=True, batch_axis=AXIS_REPL,
                                 placement=placement,
                                 block_impl=("pallas"
                                             if cfg.use_pallas_attention
                                             else "auto"))
        elif cfg.use_pallas_attention:
            from parallax_tpu.ops.pallas_attention import flash_attention
            out = flash_attention(q, k, v, causal=True)
        else:
            out = full_attention_reference(q, k, v, causal=True)
        merged = out.reshape(B, T, D)
        if tp_mode:
            merged = tp_ops.constrain(
                merged, P(AXIS_REPL, None,
                          AXIS_SHARD if tp_ops.heads_shardable(Hn)
                          else None))
            return tp_ops.row_parallel(merged, p["wo"].astype(dt),
                                       sequence_parallel=tp_sp)
        return merged @ p["wo"].astype(dt)

    def _block_apply(p, x):
        ln = p["ln1"]
        x = x + attention(
            layer_norm(x, ln["s"].astype(dt), ln["b"].astype(dt)), p)
        if tp_sp:
            x = tp_ops.seq_shard(x)
        ln = p["ln2"]
        h = layer_norm(x, ln["s"].astype(dt), ln["b"].astype(dt))
        if tp_mode:
            x = x + tp_ops.tp_mlp(h, p["w1"].astype(dt),
                                  p["w2"].astype(dt),
                                  sequence_parallel=tp_sp)
            return tp_ops.seq_shard(x) if tp_sp else x
        return x + (jax.nn.relu(h @ p["w1"].astype(dt))
                    @ p["w2"].astype(dt))

    block_apply = (jax.checkpoint(_block_apply) if cfg.remat
                   else _block_apply)

    def loss_fn(params, batch, rng):
        ids = batch["ids"]
        B, T = ids.shape
        if T > cfg.max_len:
            raise ValueError(
                f"sequence length {T} exceeds max_len {cfg.max_len}")
        mesh = emb_ops.current_mesh()
        zig = _zigzag_active(mesh, T)
        if zig:
            # Zig-zag placement happens IN-GRAPH: the user (every host)
            # feeds natural-order ids and this static gather moves each
            # token to its balanced slot — only int32 ids cross the wire
            # (4 B/token), and the same code is exact on any topology
            # (multi-host feeds stay plain process-local slices). After
            # the permute, slot j holds real position perm[j]; positions
            # and next-token labels follow the static arrays.
            n = mesh.shape[AXIS_SHARD]
            perm = zigzag_permutation(T, n)
            inv = inverse_zigzag_permutation(T, n)
            ids = jax.lax.with_sharding_constraint(
                ids[:, perm],
                jax.sharding.NamedSharding(mesh,
                                           P(AXIS_REPL, AXIS_SHARD)))
            pos_rows = perm
            label_map = inv[(perm + 1) % T]
            w_np = (perm != T - 1).astype(np.float32)
        else:
            pos_rows = np.arange(T)

        x = emb_ops.embedding_lookup(params["emb"], ids).astype(dt)
        x = x + params["pos"][pos_rows].astype(dt)[None]

        if "blocks_stacked" in params:
            from parallax_tpu.ops.pipeline import pipeline_apply
            stacked = params["blocks_stacked"]
            n_stages = (pipeline_stage_count(mesh)
                        if mesh is not None else 1)
            if mesh is None or n_stages == 1:
                # sequential fallback: apply rows in ORIGINAL layer
                # order (storage may be device-major-permuted)
                order = _layer_storage_order()
                row_of = {l: r for r, l in enumerate(order)}
                for l in range(cfg.num_layers):
                    x = block_apply(
                        jax.tree.map(lambda p: p[row_of[l]], stacked), x)
            else:
                staged, stage_fn = _stage_pipeline(stacked, n_stages)
                x = pipeline_apply(stage_fn, staged, x, mesh,
                                   cfg.num_microbatches,
                                   virtual_stages=Vp)
        else:
            for p in params["blocks"]:
                x = block_apply(p, x)
        logits = x.astype(jnp.float32) @ params["out_w"]
        if tp_mode:
            # vocab-parallel head (Megatron parallel cross-entropy
            # shape): out_w is column-sharded so each device holds
            # logits for V/tp classes; the pin keeps them sharded and
            # XLA turns the softmax/log-sum-exp reductions into psums —
            # the full [B*T, V] logits never materialize on one device
            logits = tp_ops.constrain(
                logits, P(AXIS_REPL, None, AXIS_SHARD))
        if zig:
            labels = ids[:, label_map]
            w = jnp.broadcast_to(jnp.asarray(w_np)[None],
                                 (B, T)).reshape(-1)
        else:
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.zeros((B, 1), ids.dtype)], axis=1)
            w = jnp.concatenate(
                [jnp.ones((B, T - 1)), jnp.zeros((B, 1))],
                axis=1).reshape(-1)
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.reshape(B * T, V), labels.reshape(B * T))
        loss = jnp.sum(nll * w) / jnp.sum(w)
        return loss, {"tokens": jnp.sum(w)}

    def pipeline_1f1b_vag(params, batch, rng):
        """Fused 1F1B training step (Model.value_and_grad_fn): embedding
        vjp'd outside the pipeline, stages + output head inside it, exact
        gradients for every param (ops/pipeline.pipeline_value_and_grad)."""
        ids = batch["ids"]
        B, T = ids.shape
        mesh = emb_ops.current_mesh()
        n_stages = pipeline_stage_count(mesh) if mesh is not None else 1
        if mesh is None or n_stages == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, rng),
                has_aux=True)(params)
            return loss, metrics, grads
        staged, stage_fn = _stage_pipeline(params["blocks_stacked"],
                                           n_stages)

        labels = jnp.concatenate(
            [ids[:, 1:], jnp.zeros((B, 1), ids.dtype)], axis=1)
        w = jnp.concatenate(
            [jnp.ones((B, T - 1)), jnp.zeros((B, 1))], axis=1)

        def embed(emb, pos):
            x = emb_ops.embedding_lookup(emb, ids).astype(dt)
            return x + pos[:T].astype(dt)[None]

        x, pull_embed = jax.vjp(embed, params["emb"], params["pos"])

        def mb_loss(head, out, y_mb):
            logits = out.astype(jnp.float32) @ head["out_w"]
            nll = optax.softmax_cross_entropy_with_integer_labels(
                logits.reshape(-1, logits.shape[-1]),
                y_mb["labels"].reshape(-1))
            wf = y_mb["w"].reshape(-1)
            # every row carries T-1 real tokens, so each microbatch's
            # weighted mean == its share of the global weighted mean
            return jnp.sum(nll * wf) / jnp.maximum(jnp.sum(wf), 1e-8)

        from parallax_tpu.ops.pipeline import pipeline_value_and_grad
        loss, (g_stage, g_head, g_x) = pipeline_value_and_grad(
            stage_fn, mb_loss, staged, x, {"labels": labels, "w": w},
            mesh, cfg.num_microbatches,
            head_params={"out_w": params["out_w"]},
            virtual_stages=Vp)
        g_emb, g_pos = pull_embed(g_x)
        grads = {
            "emb": g_emb, "pos": g_pos, "out_w": g_head["out_w"],
            "blocks_stacked": jax.tree.map(
                lambda g: g.reshape((cfg.num_layers,) + g.shape[2:]),
                g_stage),
        }
        return loss, {"tokens": jnp.sum(w)}, grads

    if cfg.parallelism not in ("ring", "tensor", "pipeline", "data"):
        raise ValueError(
            f"unknown parallelism {cfg.parallelism!r}; expected "
            f"'ring', 'tensor', 'pipeline' or 'data'")
    if cfg.pipeline_schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"unknown pipeline_schedule {cfg.pipeline_schedule!r}; "
            f"expected 'gpipe' or '1f1b'")
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adam(cfg.learning_rate))
    if cfg.parallelism == "pipeline":
        # layer stages over the mesh's pipeline axis (each device owns
        # num_layers/S layers), microbatch pipelining; batch dp over
        # 'repl'. The 'pipe' spec resolves to 'shard' on a 2-axis mesh
        # (core/mesh.resolve_spec), so one declaration serves both.
        # pipeline_info is the tuner's capability record: it unlocks the
        # pp > 1 half of the plan space and prices its bubble and
        # inter-stage transfers (tune/costmodel.py).
        return Model(
            init_fn, loss_fn, optimizer=tx,
            dense_params=("emb", "pos"),
            batch_specs={"ids": P(AXIS_REPL, None)},
            param_specs={"blocks_stacked/*": P(AXIS_PIPE)},
            value_and_grad_fn=(pipeline_1f1b_vag
                               if cfg.pipeline_schedule == "1f1b"
                               else None),
            pipeline_info={
                "schedule": cfg.pipeline_schedule,
                "microbatches": int(cfg.num_microbatches),
                "virtual_stages": Vp,
                "pinned_stages": (int(cfg.pipeline_stages)
                                  if cfg.pipeline_stages else None),
                "num_layers": int(cfg.num_layers),
                "model_dim": int(D),
                "act_itemsize": int(np.dtype(dt).itemsize),
            })
    if cfg.parallelism == "tensor":
        # Megatron-style TP: qkv/up-proj column-parallel, out/down-proj
        # row-parallel over 'shard'; batch data-parallel over 'repl'.
        # GSPMD partitions the matmuls and inserts the all-reduce after
        # each row-parallel kernel.
        return Model(
            init_fn, loss_fn, optimizer=tx,
            dense_params=("emb", "pos"),
            batch_specs={"ids": P(AXIS_REPL, None)},
            param_specs={
                **tp_ops.attention_param_specs("blocks/*"),
                **tp_ops.mlp_param_specs("blocks/*"),
                # vocab-parallel output head
                "out_w": P(None, AXIS_SHARD),
            })
    if cfg.parallelism == "ring":
        # dp over 'repl', sp over 'shard': [batch, seq] inputs
        # zigzag placement (if enabled) is applied in-graph by loss_fn,
        # so feeds stay natural-order process-local slices on every
        # topology — no host-side feed transform needed.
        return Model(init_fn, loss_fn, optimizer=tx,
                     dense_params=("emb", "pos"),  # replicated: lookups
                                             # follow seq-sharded ids
                     batch_specs={"ids": P(AXIS_REPL, AXIS_SHARD)})
    return Model(init_fn, loss_fn, optimizer=tx,
                 dense_params=("emb", "pos"))


def make_batch(rng: np.random.Generator, batch_size: int, seq_len: int,
               vocab_size: int):
    return {"ids": rng.integers(1, vocab_size,
                                (batch_size, seq_len)).astype(np.int32)}


# ----- KV-cached serving decode -------------------------------------------
# Incremental decode for the data-path block math above, consumed by
# serve/adapters.CausalLMDecodeProgram. Module-level (not closed over
# build_model) so the adapter can jit a fixed signature set once and
# serve with zero recompiles. The prompt prefill runs the full forward
# over the padded prompt buffer and CAPTURES each layer's K/V
# projections; the cached step then computes one position at a time
# against the stored cache — scatter-then-attend, the
# models/nmt._decode_tokens_cached shape, but pre-LN and decoder-only.
# Serve-vs-standalone bit-identity holds because both paths run these
# exact functions (see serve/adapters.standalone_greedy).


def _serve_layer_norm(x, p):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.var(x, -1, keepdims=True)
    return ((x - m) * jax.lax.rsqrt(v + 1e-6) * p["s"].astype(x.dtype)
            + p["b"].astype(x.dtype))


def _serve_attention(q, k, v, mask, num_heads):
    """Masked multi-head attention over a dense/gathered KV buffer —
    the serve decode core. Same scale and fp32-accumulation convention
    as models/nmt._attention (which the fused paged kernel
    token-matches), so the einsum and kernel executors agree."""
    B, Tq, D = q.shape
    Tk = k.shape[1]
    h = num_heads
    hd = D // h

    def split(x, T):
        return x.reshape(B, T, h, hd).transpose(0, 2, 1, 3)

    qh, kh, vh = split(q, Tq), split(k, Tk), split(v, Tk)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    scores = jnp.where(mask, scores, jnp.asarray(-1e9, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = probs.astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return out.transpose(0, 2, 1, 3).reshape(B, Tq, D)


def _prefill_embed(cfg: LongContextConfig, params, ids):
    """Prefill chunk 0: embedding + positional add over the padded
    prompt buffer ``ids`` [1, Ts]; allocates the K/V capture stacks."""
    dt = cfg.compute_dtype
    Ts = ids.shape[1]
    x = (emb_ops.embedding_lookup(params["emb"], ids).astype(dt)
         + params["pos"][:Ts].astype(dt)[None])
    z = jnp.zeros((cfg.num_layers, 1, Ts, cfg.model_dim), dt)
    return {"x": x, "pk": z, "pv": z, "ids": ids}


def _prefill_layers(cfg: LongContextConfig, params, carry, lo, hi):
    """Prefill layers ``[lo, hi)``: capture each layer's prompt K/V
    projections, then apply the pre-LN block (causal). Padded rows
    (j >= t0) compute garbage K/V — the serve insert routes them to the
    OOB sentinel so they never reach a page."""
    dt = cfg.compute_dtype
    x, pk, pv = carry["x"], carry["pk"], carry["pv"]
    B, Ts, D = x.shape
    Hn = cfg.num_heads

    def heads(z):
        return z.reshape(B, Ts, Hn, D // Hn)

    for i in range(lo, hi):
        p = params["blocks"][i]
        h = _serve_layer_norm(x, p["ln1"])
        q, k, v = jnp.split(h @ p["wqkv"].astype(dt), 3, -1)
        pk = pk.at[i].set(k)
        pv = pv.at[i].set(v)
        out = full_attention_reference(heads(q), heads(k), heads(v),
                                       causal=True)
        x = x + out.reshape(B, Ts, D) @ p["wo"].astype(dt)
        h2 = _serve_layer_norm(x, p["ln2"])
        x = x + (jax.nn.relu(h2 @ p["w1"].astype(dt))
                 @ p["w2"].astype(dt))
    return {"x": x, "pk": pk, "pv": pv, "ids": carry["ids"]}


def _prefill_finish(carry, pad_id=0):
    """Final prefill chunk: the per-request decode state. ``base`` is
    the position of the LAST prompt token (t0 - 1): decode step 0
    consumes that token (``first``) at position ``base`` and emits the
    first generated token, so step t writes position base + t."""
    ids = carry["ids"]
    t0 = jnp.sum((ids[0] != pad_id).astype(jnp.int32))
    base = (t0 - 1).astype(jnp.int32)
    first = jnp.take(ids[0], base, mode="clip").astype(jnp.int32)
    return {"pk": carry["pk"], "pv": carry["pv"],
            "base": base[None], "first": first[None]}


def _decode_step_cached(cfg: LongContextConfig, params, tok, t, base,
                        first, kc, vc, pages=None, page_size=None,
                        attn_impl=None):
    """One batched cached decoder step: ``tok``/``t``/``base``/``first``
    are [S] per-slot rows; returns (logits [S, V] f32, kc, vc). Step 0
    swaps in ``first`` (the last prompt token) for the scheduler-fed
    BOS; position = base + t. ``pages`` [S, P] selects the paged pool
    layout [L, pool_pages, page_size, D] (dense: [L, S, Tbuf, D]);
    ``attn_impl`` routes the paged executor exactly as in
    models/nmt._decode_tokens_cached — the PR 16 kernel serves this
    adapter unchanged. Row-wise math only: slots are independent."""
    dt = cfg.compute_dtype
    D = cfg.model_dim
    S = tok.shape[0]
    paged = pages is not None
    if paged:
        # lazy: ops -> models would be circular the other way round
        from parallax_tpu.ops import pallas_paged_attention as _ppa
        pool, ps = kc.shape[1], int(page_size)
        Tbuf = pages.shape[1] * ps
        impl = _ppa.resolve_impl(
            attn_impl, G=1, D=D, page_size=ps,
            num_heads=cfg.num_heads,
            itemsize=jnp.dtype(dt).itemsize)
    else:
        Tbuf = kc.shape[2]
        rows = jnp.arange(S)
    tok_eff = jnp.where(t == 0, first, tok)
    pos = (base + t)[:, None]                                # [S, 1]
    # clip: a slot at its cap may address one position past the buffer
    # before it retires host-side; the output is discarded but must
    # stay finite
    pos_emb = jnp.take(params["pos"].astype(dt), pos, axis=0,
                       mode="clip")                          # [S, 1, D]
    x = (emb_ops.embedding_lookup(params["emb"],
                                  tok_eff[:, None]).astype(dt)
         + pos_emb)                                          # [S, 1, D]
    mask = (jnp.arange(Tbuf)[None, :] <= pos)[:, None, None, :]
    if paged:
        pg, off = _ppa.sentinel_write_coords(pages, pos, ps, pool)
    for i, p in enumerate(params["blocks"]):
        h = _serve_layer_norm(x, p["ln1"])
        q, k_t, v_t = jnp.split(h @ p["wqkv"].astype(dt), 3, -1)
        if paged:
            kc = kc.at[i, pg, off].set(k_t, mode="drop")
            vc = vc.at[i, pg, off].set(v_t, mode="drop")
            if impl == "kernel":
                y = _ppa.paged_decode_attention(
                    q, kc[i], vc[i], pages, pos,
                    num_heads=cfg.num_heads, page_size=ps,
                    impl="kernel")
            else:
                k_all = _ppa.paged_gather(kc[i], pages)
                v_all = _ppa.paged_gather(vc[i], pages)
                y = _serve_attention(q, k_all, v_all, mask,
                                     cfg.num_heads)
        else:
            kc = kc.at[i, rows[:, None], pos].set(k_t, mode="drop")
            vc = vc.at[i, rows[:, None], pos].set(v_t, mode="drop")
            y = _serve_attention(q, kc[i], vc[i], mask, cfg.num_heads)
        x = x + y @ p["wo"].astype(dt)
        h2 = _serve_layer_norm(x, p["ln2"])
        x = x + (jax.nn.relu(h2 @ p["w1"].astype(dt))
                 @ p["w2"].astype(dt))
    logits = x[:, 0].astype(jnp.float32) @ params["out_w"]
    return logits, kc, vc


def _init_serve_self_cache(cfg: LongContextConfig, batch: int,
                           max_len: int):
    z = jnp.zeros((cfg.num_layers, batch, max_len, cfg.model_dim),
                  cfg.compute_dtype)
    return z, z


def _init_serve_paged_cache(cfg: LongContextConfig, pool_pages: int,
                            page_size: int):
    z = jnp.zeros((cfg.num_layers, pool_pages, page_size,
                   cfg.model_dim), cfg.compute_dtype)
    return z, z
