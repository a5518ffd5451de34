"""Mixture-of-experts layer with expert parallelism over the mesh.

Expert parallelism is absent from the reference (SURVEY.md §2.5) — this is
a TPU-native extension rounding out the parallelism inventory: experts are
sharded over the ``'shard'`` mesh axis (one group of experts per device
slice) and tokens are routed to their experts with a capacity-bounded
``all_to_all`` dispatch/combine, the standard TPU MoE shape (static
shapes, no dynamic-size tensors under jit).

Layout:
  * expert weights: [E, D, F] sharded P('shard', None, None) — each
    device holds E/n experts;
  * tokens: [G, C, D] where G = groups (= data shards), C = capacity —
    dispatched via all_to_all over the expert axis;
  * router: dense [D, E], replicated. ``top_k=1`` is switch routing
    (Fedus et al.: gate = raw router prob of the winner); ``top_k>=2``
    is GShard-style routing (gates renormalized over the selected
    experts, earlier choices get capacity priority).

Capacity overflow is NEVER silent: every call returns the dropped
(token, choice) fraction so training loops can watch it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from parallax_tpu.core.mesh import AXIS_REPL, AXIS_SHARD


class MoEOut(NamedTuple):
    out: jax.Array        # [B, D]
    aux_loss: jax.Array   # scalar load-balance loss (Shazeer et al.)
    dropped: jax.Array    # scalar: fraction of (token, choice) slots
                          # dropped by the capacity bound (0 on the
                          # dense fallback path)


def switch_moe(tokens: jax.Array,          # [B, D] (batch sharded dim 0)
               router_w: jax.Array,        # [D, E] replicated
               expert_w1: jax.Array,       # [E, D, F] row(expert)-sharded
               expert_w2: jax.Array,       # [E, F, D] row(expert)-sharded
               mesh: Optional[Mesh],
               capacity_factor: float = 1.25,
               top_k: int = 1,
               ) -> MoEOut:
    """Top-k MoE (k=1: switch; k>=2: GShard top-k with renormalized
    gates and first-choice capacity priority).

    Without a mesh (single device / reference path) the same math runs
    unsharded; with a mesh the experts are sharded over 'shard' and
    dispatch/combine run as all_to_all over that axis.
    """
    B, D = tokens.shape
    E = router_w.shape[1]
    k = int(top_k)
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} must be in [1, {E}]")

    logits = tokens.astype(jnp.float32) @ router_w    # [B, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, top_idx = jax.lax.top_k(probs, k)      # [B, k]
    if k == 1:
        gates = top_probs                              # switch: raw prob
    else:
        gates = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)

    # load-balancing auxiliary loss: E * sum_e f_e * p_e, with f_e the
    # fraction of routing assignments (all k choices) sent to expert e
    density = jnp.zeros((E,))
    for c in range(k):
        density = density + jnp.mean(jax.nn.one_hot(top_idx[:, c], E),
                                     axis=0)
    density = density / k
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(density * mean_prob)

    n = mesh.shape[AXIS_SHARD] if mesh is not None else 1
    if mesh is None or n == 1 or E % n != 0:
        if mesh is not None and n > 1 and E % n != 0:
            # mirrors the engine's param_specs graceful fallback: an
            # indivisible expert count runs the replicated dense path
            from parallax_tpu.common.lib import parallax_log
            parallax_log.warning(
                "switch_moe: %d experts not divisible by shard axis %d; "
                "running the replicated (non-EP) path", E, n)
        out = _expert_compute_dense(tokens, top_idx, gates, expert_w1,
                                    expert_w2)
        return MoEOut(out, aux_loss, jnp.zeros((), jnp.float32))
    # capacity is per (device, expert) dispatch slots: balanced load puts
    # k * local_b / E assignments on each expert per device
    local_b = B // int(np.prod(list(mesh.shape.values())))
    capacity = max(1, int(np.ceil(capacity_factor * k * local_b / E)))

    def local(tokens_l, idx_l, gate_l, w1_l, w2_l):
        # tokens_l: [b, D]; idx_l/gate_l: [b, k]; w1_l: [E/n, D, F]
        b = tokens_l.shape[0]
        e_per = E // n
        # flatten choices with FIRST choices ahead in the cumsum so they
        # win capacity slots over second choices (GShard priority)
        idx_f = idx_l.T.reshape(-1)                            # [k*b]
        onehot = jax.nn.one_hot(idx_f, E, dtype=jnp.int32)     # [k*b, E]
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1
        pos_in_expert = jnp.max(pos, axis=1)                   # [k*b]
        keep = pos_in_expert < capacity
        toks_f = jnp.tile(tokens_l, (k, 1))                    # [k*b, D]
        # dispatch buffer: [E, capacity, D]
        disp = jnp.zeros((E, capacity, D), tokens_l.dtype)
        safe_pos = jnp.where(keep, pos_in_expert, 0)
        disp = disp.at[idx_f, safe_pos].add(
            jnp.where(keep[:, None], toks_f, 0))
        # ship each expert group to its owner shard: regroup [E, C, D] as
        # [n, e_per, C, D] (dim0 = owner shard), exchange chunks; after
        # the all_to_all, recv[s'] holds peer s' tokens for MY experts
        disp = disp.reshape(n, e_per, capacity, D)
        recv = jax.lax.all_to_all(disp, AXIS_SHARD, split_axis=0,
                                  concat_axis=0, tiled=True)
        # [n, e_per, C, D] -> per-expert token matrix [e_per, n*C, D]
        x_e = recv.transpose(1, 0, 2, 3).reshape(e_per, n * capacity, D)
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", x_e,
                                   w1_l.astype(x_e.dtype)))
        y_e = jnp.einsum("ecf,efd->ecd", h, w2_l.astype(x_e.dtype))
        # route results back to the shards that own the tokens
        back = y_e.reshape(e_per, n, capacity, D).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(back, AXIS_SHARD, split_axis=0,
                                 concat_axis=0, tiled=True)
        # out[s', j] = my tokens' outputs from expert (s', j)
        out = out.reshape(E, capacity, D)
        # combine: each (token, choice) reads its slot, gate-weighted
        got = out[idx_f, safe_pos]                             # [k*b, D]
        got = jnp.where(keep[:, None], got, 0)
        gate_f = gate_l.T.reshape(-1)                          # [k*b]
        combined = (got * gate_f[:, None].astype(got.dtype)
                    ).reshape(k, b, D).sum(0)
        drop_ct = jnp.sum(1.0 - keep.astype(jnp.float32))
        return combined, drop_ct.reshape(1)

    out, drop_ct = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P((AXIS_REPL, AXIS_SHARD), None),
                  P((AXIS_REPL, AXIS_SHARD), None),
                  P((AXIS_REPL, AXIS_SHARD), None),
                  P(AXIS_SHARD, None, None),
                  P(AXIS_SHARD, None, None)),
        out_specs=(P((AXIS_REPL, AXIS_SHARD), None),
                   P((AXIS_REPL, AXIS_SHARD))),
    )(tokens, top_idx, gates, expert_w1, expert_w2)
    dropped = jnp.sum(drop_ct) / (k * B)
    return MoEOut(out, aux_loss, dropped)


def _expert_compute_dense(tokens, top_idx, gates, w1, w2):
    """Unsharded reference path: every expert computed for its tokens via
    multi-hot masking (small E); no capacity bound, so nothing drops."""
    h = jnp.einsum("bd,edf->bef", tokens, w1.astype(tokens.dtype))
    h = jax.nn.relu(h)
    out_all = jnp.einsum("bef,efd->bed", h, w2.astype(tokens.dtype))
    E = w1.shape[0]
    sel = jnp.zeros((tokens.shape[0], E), tokens.dtype)
    for c in range(top_idx.shape[1]):
        sel = sel + (jax.nn.one_hot(top_idx[:, c], E, dtype=tokens.dtype)
                     * gates[:, c:c + 1].astype(tokens.dtype))
    out = jnp.einsum("bed,be->bd", out_all, sel)
    return out
