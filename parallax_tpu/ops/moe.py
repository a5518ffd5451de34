"""Mixture-of-experts layers: two entries.

``switch_moe`` — the capacity-bounded layer with expert parallelism
over the mesh. Experts are sharded over the ``'shard'`` mesh axis (one
group of experts per device slice) and tokens are routed to their
experts with a capacity-bounded ``all_to_all`` dispatch/combine, the
standard TPU MoE shape (static shapes, no dynamic-size tensors under
jit). **It DROPS the (token, choice) slots past the capacity factor**
under a mesh, and says how many. Without a mesh (one device) it takes
``_expert_compute_dense``, which **computes EVERY expert for EVERY
token** and masks: exact and dropless, but E times the work, so small
sizes only. Two-matrix ReLU experts, top-1 / top-2.

``routed_experts`` — the dropless layer of a chip that holds a range of
the experts. **Routing is the caller's**: it hands over, for every
token, the ``k`` experts it chose among all ``E`` and the gate of each
(``linear_router`` is the usual one: one matrix, softmax, top-k,
renormalised gates, the load-balance loss; ``models/zaya`` brings an
MLP's ``argmax(p + bias)`` with the unrenormalised ``p`` as its gate).
The chip computes the part of the result its own ``[first_expert,
first_expert + held)`` experts give, for exactly the rows routed to
them, grouped by expert (a grouped matrix product: ``megablox.gmm`` on
the TPU, ``lax.ragged_dot`` elsewhere). **It never drops**: its buffers
hold the worst case (every choice of every token), the products visit
only the rows routed here. Three-matrix gated (SwiGLU) experts. What
the absent experts would add is left out; on one device ``first_expert``
is an argument, under expert parallelism it follows the shard index
(``jax.lax.axis_index``), and the exchange that would bring other chips'
tokens here is not part of it.

``switch_moe`` layout:
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

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from parallax_tpu.core.mesh import AXIS_REPL, AXIS_SHARD


class MoEOut(NamedTuple):
    out: jax.Array        # [B, D]
    aux_loss: jax.Array   # scalar load-balance loss (Shazeer et al.)
    dropped: jax.Array    # scalar: fraction of (token, choice) slots
                          # dropped by the capacity bound (0 on the
                          # dense fallback path)


def load_balance_loss(probs: jax.Array, top_idx: jax.Array) -> jax.Array:
    """Load-balancing auxiliary loss (Shazeer et al.): ``E * sum_e f_e *
    p_e``, with ``f_e`` the fraction of routing assignments (all k
    choices of ``top_idx [B, k]``) sent to expert e and ``p_e`` the mean
    router probability of e (``probs [B, E]``)."""
    E, k = probs.shape[1], top_idx.shape[1]
    density = jnp.zeros((E,))
    for c in range(k):
        density = density + jnp.mean(jax.nn.one_hot(top_idx[:, c], E),
                                     axis=0)
    density = density / k
    return E * jnp.sum(density * jnp.mean(probs, axis=0))


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

    aux_loss = load_balance_loss(probs, top_idx)

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


# ---------------------------------------------------------------------------
# The dropless layer of a chip that holds a range of the experts.
# ---------------------------------------------------------------------------

class RoutedOut(NamedTuple):
    out: jax.Array          # [B, D]: the held experts' part of the result
    dropped: jax.Array      # (token, choice) rows routed here that no
                            # part's grouped products covered (must be 0)
    rows_here: jax.Array    # rows routed to the held experts
    load_max_over_mean: jax.Array   # fullest held expert over their mean


class LinearRoute(NamedTuple):
    choice: jax.Array       # int [B, k]: the top-k experts of all E
    gate: jax.Array         # float32 [B, k]: their probabilities,
                            # renormalised to one
    aux_loss: jax.Array     # scalar load-balance loss over all E experts


# megablox tiles (rows, contraction, columns): the largest of these that
# divides the size, so that an expert's 2048 x 768 matrix is two tiles
# and not ninety-six
def _gmm_tiling(m: int, k: int, n: int):
    def fit(size, choices):
        return next((c for c in choices if size % c == 0), size)
    wide = (1024, 896, 768, 512, 256, 128)
    return fit(m, (512, 256, 128)), fit(k, wide), fit(n, wide)


# The sorted rows are taken in two parts: the first, this many times a
# balanced router's share of the rows, in every step; the remainder only
# in a step whose rows reach into it (``routed_experts``).
_FAST_ROWS_FACTOR = 2.0


def fast_rows(num_tokens: int, top_k: int, held: int,
              num_experts: int) -> int:
    """How many of the sorted (token, choice) rows ``routed_experts``
    computes in every step; a step that routes more rows than this to
    the held experts also runs its second part."""
    pairs = num_tokens * top_k
    share = -(-pairs * held // num_experts)
    return min(pairs, -(-int(_FAST_ROWS_FACTOR * share) // 512) * 512)


def _grouped_dot(lhs, rhs, group_sizes, impl, transpose_rhs=False):
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group; rows
    past ``sum(group_sizes)`` are nobody's and come back unspecified
    (the caller masks them). Visits only the rows the groups hold."""
    if impl == "ragged_dot":
        if transpose_rhs:
            rhs = rhs.swapaxes(1, 2)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu import megablox
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype,
                        _gmm_tiling(lhs.shape[0], lhs.shape[1], n),
                        None, None, transpose_rhs, impl == "gmm_interpret")


def _slots(inv, lo: int, rows: int):
    """For every (token, choice) pair its place among the sorted rows
    ``[lo, lo + rows)`` and whether it has one."""
    pos = inv - lo
    return jnp.clip(pos, 0, rows - 1), (pos >= 0) & (pos < rows)


def _gather_pairs(y, inv, lo: int, k: int):
    """``[B, k, D]``: for every (token, choice) pair its row of ``y``
    (the sorted rows ``[lo, lo + M)``), zeros where it has none."""
    pos, valid = _slots(inv, lo, y.shape[0])
    pairs = jnp.where(valid[:, None], y[pos], jnp.zeros((), y.dtype))
    return pairs.reshape(-1, k, y.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(tokens, token_of_row, inv, lo: int, k: int):
    """``tokens[token_of_row]``: the rows ``[lo, lo + M)`` of the sorted
    (token, choice) pairs, each a copy of its token. The cotangent comes
    back by a gather through ``inv`` (the pair's place among the sorted
    rows) where plain AD would scatter-add ``M`` rows."""
    return tokens[token_of_row]


def _dispatch_fwd(tokens, token_of_row, inv, lo, k):
    return tokens[token_of_row], inv


def _dispatch_bwd(lo, k, res, g):
    d_tokens = jnp.sum(_gather_pairs(g, res, lo, k).astype(jnp.float32),
                       axis=1).astype(g.dtype)
    return d_tokens, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combine(y, weight, inv, token_of_row, choice_of_row, lo: int, k: int):
    """``out[b] = sum_c weight[b, c] * y[row of pair (b, c)]`` over the
    pairs that have a row in ``[lo, lo + M)``: float32 ``[B, D]``. Its
    cotangents are gathers too."""
    return jnp.einsum("bkd,bk->bd",
                      _gather_pairs(y, inv, lo, k).astype(jnp.float32),
                      weight)


def _combine_fwd(y, weight, inv, token_of_row, choice_of_row, lo, k):
    out = _combine(y, weight, inv, token_of_row, choice_of_row, lo, k)
    return out, (y, weight, inv, token_of_row, choice_of_row)


def _combine_bwd(lo, k, res, g):
    y, weight, inv, token_of_row, choice_of_row = res
    w_row = weight[token_of_row, choice_of_row]                  # [M]
    d_y = (g[token_of_row] * w_row[:, None]).astype(y.dtype)
    d_w = jnp.einsum("bkd,bd->bk",
                     _gather_pairs(y, inv, lo, k).astype(jnp.float32), g)
    return d_y, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _part_sizes(sizes, ends, lo: int, n: int):
    """How many of each held expert's rows lie among the sorted rows
    ``[lo, lo + n)``: the group sizes of that part's grouped products."""
    return jnp.clip(ends, lo, lo + n) - jnp.clip(ends - sizes, lo, lo + n)


def _rows(tokens, weight, w_gate, w_up, w_down, route, lo: int, n: int,
          k: int, impl: str):
    """What the held experts give for the sorted rows ``[lo, lo + n)``
    of ``route`` (``order``, its inverse, the held experts' row counts
    and their running sum), combined per token: float32 ``[B, D]``. The
    rows past the last held expert's are the absent experts': the
    products skip them and leave them unspecified, so they are zeroed on
    the way in and on the way out, forward and (by the same selects)
    backward."""
    order, inv, sizes, ends = route
    mine = order[lo:lo + n]
    token_of_row, choice_of_row = mine // k, mine % k
    part = _part_sizes(sizes, ends, lo, n)
    live = (lo + jnp.arange(n) < ends[-1])[:, None]

    def only_live(a):
        return jnp.where(live, a, jnp.zeros((), a.dtype))

    x = only_live(_dispatch(tokens, token_of_row, inv, lo, k))
    gate_act = only_live(_grouped_dot(x, w_gate, part, impl))
    up = only_live(_grouped_dot(x, w_up, part, impl))
    if lo == 0:
        # kept for the backward pass where a caller's checkpoint
        # policy says so, in place of the products that made them
        x = checkpoint_name(x, "moe_rows")
        gate_act = checkpoint_name(gate_act, "moe_rows")
        up = checkpoint_name(up, "moe_rows")
    h = (jax.nn.silu(gate_act.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(x.dtype)
    y = only_live(_grouped_dot(h, w_down, part, impl))
    if lo == 0:
        y = checkpoint_name(y, "moe_rows")
    return _combine(y, weight, inv, token_of_row, choice_of_row, lo, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _rows_if(reached, tokens, weight, w_gate, w_up, w_down, route, lo: int,
             n: int, k: int, impl: str):
    """``_rows`` in a step where ``reached`` (the held experts' rows
    reach past ``lo``), zeros where not. The backward pass is a ``cond``
    of its own that runs the rows again: plain AD would carry every row
    buffer of the part out of the forward ``cond`` as a residual, and
    the branch not taken would fill all of them with zeros in every
    step."""
    return jax.lax.cond(
        reached,
        lambda: _rows(tokens, weight, w_gate, w_up, w_down, route, lo, n,
                      k, impl),
        lambda: jnp.zeros(tokens.shape, jnp.float32))


def _rows_if_fwd(reached, tokens, weight, w_gate, w_up, w_down, route, lo,
                 n, k, impl):
    args = (tokens, weight, w_gate, w_up, w_down)
    return _rows_if(reached, *args, route, lo, n, k, impl), \
        (reached, args, route)


def _rows_if_bwd(lo, n, k, impl, res, g):
    reached, args, route = res

    def pull():
        return jax.vjp(lambda *a: _rows(*a, route, lo, n, k, impl),
                       *args)[1](g)

    grads = jax.lax.cond(
        reached, pull, lambda: tuple(jnp.zeros_like(a) for a in args))
    return (None, *grads, None)


_rows_if.defvjp(_rows_if_fwd, _rows_if_bwd)


def linear_router(tokens: jax.Array,         # [B, D]
                  router_w: jax.Array,       # [D, E]: all E experts
                  top_k: int,
                  choice: Optional[jax.Array] = None) -> LinearRoute:
    """The one-matrix router: ``softmax(tokens @ router_w)`` over all
    ``E`` experts in float32, its top-k, their probabilities
    renormalised to one, and the load-balance loss. A given ``choice``
    (int ``[B, k]``) takes the top-k's place: its experts' probabilities
    renormalised, its loads in the loss (a comparison under one
    routing)."""
    E = router_w.shape[1]
    k = int(top_k)
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} must be in [1, {E}]")
    probs = jax.nn.softmax(tokens.astype(jnp.float32)
                           @ router_w.astype(jnp.float32), axis=-1)
    if choice is None:
        top_probs, top_idx = jax.lax.top_k(probs, k)           # [B, k]
    else:
        top_idx = choice
        top_probs = jnp.take_along_axis(probs, choice, axis=-1)
    gates = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
    return LinearRoute(top_idx, gates, load_balance_loss(probs, top_idx))


def routed_experts(tokens: jax.Array,        # [B, D]
                   choice: jax.Array,        # int [B, k] among all E
                   gate: jax.Array,          # [B, k]
                   w_gate: jax.Array,        # [held, D, F]
                   w_up: jax.Array,          # [held, D, F]
                   w_down: jax.Array,        # [held, F, D]
                   num_experts: int,
                   first_expert=0,
                   impl: Optional[str] = None) -> RoutedOut:
    """Routed gated experts without capacity and without drops, for the
    experts ``[first_expert, first_expert + held)`` this chip holds,
    under the caller's routing: ``sum_{c < k, choice[t, c] held}
    gate[t, c] * w_down[e] (silu(w_gate[e] y_t) * w_up[e] y_t)`` with
    ``e = choice[t, c]``. The gates are taken as they come (float32,
    differentiable); ``choice`` carries no gradient. The experts compute
    in ``tokens.dtype``.

    The (token, choice) rows are sorted by held expert, the rows of
    absent experts last, and are taken in two parts: the first
    ``_FAST_ROWS_FACTOR`` times a balanced router's share of the rows
    (``fast_rows``, which is why ``num_experts`` is asked for) always,
    the remainder only in a step whose rows reach into it. ``dropped``
    counts the rows routed here that neither part covered, off the
    parts' own group sizes and the predicate the second part ran under:
    0 unless the split loses rows. In each part the three products run
    as grouped matrix products over the rows routed here (``impl``:
    ``"gmm"`` the megablox kernel, the default on a TPU;
    ``"ragged_dot"`` XLA's, the default elsewhere; ``"gmm_interpret"``
    the kernel interpreted). ``first_expert`` may be a traced scalar (a
    shard's index times ``held``)."""
    B, D = tokens.shape
    E = int(num_experts)
    held = w_gate.shape[0]
    k = choice.shape[1]
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} must be in [1, {E}]")
    if impl is None:
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged_dot"
    if impl not in ("gmm", "gmm_interpret", "ragged_dot"):
        raise ValueError(f"unknown impl {impl!r}")

    local = choice - first_expert
    here = (local >= 0) & (local < held)                        # [B, k]
    weight = jnp.where(here, gate, 0.0).astype(jnp.float32)
    # absent experts' rows sort last, under the sentinel group `held`
    group = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)   # [B * k]
    inv = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0)
    ends = jnp.cumsum(sizes)
    rows_here = ends[-1]

    # Every choice of every token has a row, so nothing can overflow;
    # but the rows a balanced router sends here are held / E of them.
    # The first `fast` sorted rows (a few times that share) are always
    # computed; the rest, which only a collapsed router fills, run
    # under a `cond` that costs nothing while they are empty.
    fast = fast_rows(B, k, held, E)
    dt = tokens.dtype
    route = (order, inv, sizes, ends)
    operands = (tokens, weight, w_gate.astype(dt), w_up.astype(dt),
                w_down.astype(dt), route)
    out = _rows(*operands, 0, fast, k, impl)
    covered = jnp.sum(_part_sizes(sizes, ends, 0, fast))
    if fast < B * k:
        reached = rows_here > fast
        out = out + _rows_if(reached, *operands, fast, B * k - fast, k,
                             impl)
        covered = covered + jnp.where(
            reached, jnp.sum(_part_sizes(sizes, ends, fast, B * k - fast)),
            0)
    out = out.astype(dt)

    dropped = (rows_here - covered).astype(jnp.float32)
    mean_load = jnp.maximum(rows_here.astype(jnp.float32) / held, 1e-9)
    return RoutedOut(out, dropped, rows_here.astype(jnp.float32),
                     jnp.max(sizes).astype(jnp.float32) / mean_load)
