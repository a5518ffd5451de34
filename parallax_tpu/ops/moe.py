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
renormalised gates, the load-balance loss; ``sigmoid_router`` scores
each expert by itself, chooses under balancing biases that select and do
not weigh, and returns every expert's load for ``balance_step``, the
auxiliary-loss-free rule that moves those biases; ``models/zaya`` brings
an MLP's ``argmax(p + bias)`` with the unrenormalised ``p`` as its gate
and the same rule).
The chip computes the part of the result its own ``[first_expert,
first_expert + held)`` experts give, for exactly the rows routed to
them, grouped by expert (a grouped matrix product: ``megablox.gmm`` on
the TPU, ``lax.ragged_dot`` elsewhere). **It never drops**: its row
buffers hold the worst case (every choice of every token), the products
and the per-token sums (``sum_rows_by_token``: the combine, and the
dispatch's cotangent) visit only the rows routed here, and nothing is
held by (token, choice) pair. Three-matrix gated (SwiGLU) experts. What
the absent experts would add is left out; on one device ``first_expert``
is an argument, under expert parallelism it follows the shard index
(``jax.lax.axis_index``), and the exchange that would bring other chips'
tokens here is not part of it. ``shared_expert`` is the SwiGLU every
token takes beside its routed ones: every chip of an expert-parallel
group computes it alike, so where the chips' shares are added it counts
ONCE.

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

# what a caller's checkpoint policy keeps so that no grouped product runs
# twice: ``routed_experts``' row buffers
KEPT = "moe_rows"


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
    rows_walked: jax.Array  # sorted rows the per-token sums visited: the
                            # live ones, rounded up to the kernel's row
                            # block in each part where the kernel runs
    load_max_over_mean: jax.Array   # fullest held expert over their mean


class LinearRoute(NamedTuple):
    choice: jax.Array       # int [B, k]: the top-k experts of all E
    gate: jax.Array         # float32 [B, k]: their probabilities,
                            # renormalised to one
    aux_loss: jax.Array     # scalar load-balance loss over all E experts


class SigmoidRoute(NamedTuple):
    choice: jax.Array       # int [B, k]: the top-k of ``score + bias``,
                            # or the choice that was given
    gate: jax.Array         # float32 [B, k]: the chosen scores WITHOUT
                            # the bias, normalised and scaled
    load: jax.Array         # float32 [E]: the (token, choice) pairs sent
                            # to each of ALL the experts
    gate_sum_mean: jax.Array    # the mean over tokens of the chosen
                                # scores' sum, before any normalisation
    own_choice: jax.Array   # int [B, k]: the top-k of ``score + bias``
                            # whatever choice was given


# megablox tiles (rows, contraction, columns): the largest that divides
# the size, of 512, 256 and 128 rows and of the multiples of 128 up to
# 1,024 across, so that an expert's 2048 x 768 matrix is two tiles and
# not ninety-six. megablox evaluates the rule once for each product at
# that product's own (m, k, n): the forward's, and in its VJP the
# transposed product's (k and n swapped) and ``tgmm``'s
def _gmm_tiling(m: int, k: int, n: int):
    def fit(size, choices):
        return next((c for c in choices if size % c == 0), size)
    wide = range(1024, 0, -128)
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
    (the caller masks them). Visits only the rows the groups hold.
    megablox is handed the tile rule itself, so that each of the three
    products, this one and its VJP's two, is tiled for its own shape."""
    if impl == "ragged_dot":
        if transpose_rhs:
            rhs = rhs.swapaxes(1, 2)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu import megablox
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling,
                        None, None, transpose_rhs, impl == "gmm_interpret")


# What the per-token sums' kernel may take of a TPU's VMEM (a v5e has
# 128 MiB), and of that its float32 accumulator, one buffer of it.
# Alone on a v5e at Mellum2's shapes (16.4 k live rows of 2,304) the
# whole width in one chunk took 0.38 ms a call, two chunks of 1,152
# 0.52, three of 768 0.61 (PERF.md section 6, PR 34): a row's update
# waits on the row before it whatever the width.
_SUM_ROWS_VMEM_BYTES = 100 * 1024 * 1024
_SUM_ROWS_ACC_BYTES = 80 * 1024 * 1024


def _sum_rows_block(m: int) -> int:
    """``sum_rows_by_token``'s row block: the rows as the grouped
    products take them."""
    return next((c for c in (512, 256, 128) if m % c == 0), m)


def _sum_rows_chunk(num_tokens: int, dim: int) -> int:
    """``sum_rows_by_token``'s column chunk: the widest multiple of 128
    lanes that divides ``dim`` and keeps ``[num_tokens, chunk]`` float32
    under ``_SUM_ROWS_ACC_BYTES``."""
    chunks = [c for c in range(dim, 0, -128) if dim % c == 0] \
        if dim % 128 == 0 else [dim]
    return next((c for c in chunks
                 if 4 * num_tokens * c <= _SUM_ROWS_ACC_BYTES), chunks[-1])


def _sum_rows_kernel(nl_ref, tok_ref, *refs, tm: int, scaled: bool):
    from jax.experimental import pallas as pl

    if scaled:
        scale_ref, rows_ref, out_ref, buf = refs
    else:
        rows_ref, out_ref, buf = refs
    block = pl.program_id(1)
    base = block * tm
    n_live = nl_ref[0]

    @pl.when(block == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(base < n_live)
    def _():
        # widened once a block; what lies past the live rows adds zeros
        index = base + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        buf[...] = jnp.where(index < n_live,
                             rows_ref[...].astype(jnp.float32), 0.0)

        # eight rows a trip, in row order: two rows of one token (the
        # last of an expert's group and the first of the next) are two
        # updates of one accumulator row, the second on the first's
        def eight(group, carry):
            first = pl.multiple_of(group * 8, 8)
            for u in range(8):
                r = first + u
                row = buf[pl.ds(r, 1), :]
                if scaled:
                    row = scale_ref[base + r] * row
                token = pl.ds(tok_ref[base + r], 1)
                out_ref[token, :] = out_ref[token, :] + row
            return carry
        jax.lax.fori_loop(
            0, (jnp.minimum(tm, n_live - base) + 7) // 8, eight, 0)


def sum_rows_by_token(rows, scale, token_of_row, n_live, num_tokens: int,
                      impl: str):
    """float32 ``[num_tokens, D]``: the sum over the rows ``r < n_live``
    of ``scale[r] * rows[r]`` (of ``rows[r]`` where ``scale`` is None)
    into row ``token_of_row[r]``, each row widened to float32 before it
    is scaled and added. The work follows ``n_live``, not ``M``. Relies
    on: ``rows [M, D]`` with the live ones a prefix (``0 <= n_live <=
    M``; a row at or past ``n_live`` is never added, whatever it holds);
    ``token_of_row [M]`` in ``[0, num_tokens)`` for EVERY row, live or
    not; ``scale [M]`` float32 and finite; ``M`` a multiple of 8. A
    token's rows are added in row order.

    ``impl`` as ``routed_experts`` takes it: ``"ragged_dot"`` is
    ``jax.ops.segment_sum`` over the masked rows; ``"gmm"`` the Mosaic
    kernel ``sum_rows`` (``"gmm_interpret"``: interpreted), a grid of
    (column chunks, row blocks): the chunk's float32 accumulator
    ``[num_tokens, chunk]`` stays in VMEM across the row blocks and is
    written once, a ``[tm, chunk]`` block of rows streams in for every
    live block (dead blocks re-use the last live block: nothing is
    fetched), and every live row is one read-modify-write of its
    token's row of the accumulator, its token and scale read off SMEM.
    It visits ``n_live`` rounded up to ``tm`` rows."""
    M, D = rows.shape
    n_live = jnp.reshape(n_live, (1,)).astype(jnp.int32)
    if impl == "ragged_dot":
        wide = rows.astype(jnp.float32)
        if scale is not None:
            wide = wide * scale[:, None]
        wide = jnp.where((jnp.arange(M) < n_live)[:, None], wide, 0.0)
        return jax.ops.segment_sum(wide, token_of_row,
                                   num_segments=num_tokens)
    # imported where a kernel is traced (``ops/sparse_optim``)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if M % 8:
        raise ValueError(f"sum_rows_by_token: {M} rows, not a multiple of 8")
    tm, dc = _sum_rows_block(M), _sum_rows_chunk(num_tokens, D)

    def rows_map(chunk, block, nl_ref, *_):
        last = jnp.maximum((nl_ref[0] + tm - 1) // tm - 1, 0)
        return jnp.minimum(block, last), chunk

    prefetch = (n_live, token_of_row.astype(jnp.int32))
    if scale is not None:
        prefetch += (scale.astype(jnp.float32),)
    return pl.pallas_call(
        functools.partial(_sum_rows_kernel, tm=tm, scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(D // dc, M // tm),
            in_specs=[pl.BlockSpec((tm, dc), rows_map)],
            out_specs=pl.BlockSpec((num_tokens, dc),
                                   lambda chunk, block, *_: (0, chunk),
                                   pipeline_mode=pl.Buffered(1)),
            scratch_shapes=[pltpu.VMEM((tm, dc), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_tokens, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_SUM_ROWS_VMEM_BYTES),
        name="sum_rows",
        interpret=impl == "gmm_interpret",
    )(*prefetch, rows)


def _one_row_a_token(m: int, num_tokens: int, k: int) -> bool:
    """Whether a part of ``m`` sorted rows holds exactly one row of every
    token: one choice a token and all of them in the part
    (``models/zaya``: top-1, half of the experts held)."""
    return k == 1 and m == num_tokens


def _sum_by_token(rows, scale, mine, n_live, num_tokens: int, k: int,
                  impl: str):
    """``sum_rows_by_token`` for the sorted rows of a part (``mine
    [M]``: the pair ``b * k + c`` of every row, so its token is ``mine
    // k``). Where the part holds one row of every token the rows are a
    permutation of the tokens and there are no pairs to outnumber them:
    the sum is each token's own row, fetched by a gather that XLA fuses
    into what reads it (zeros where the row is not live), where the
    kernel would write float32 ``[B, D]`` for that to read back. Read
    off the static shapes alone; on a v5e ZAYA's cell lost 1.4 % with
    the kernel there, Mellum2's and Keye's (8 pairs a token for 2 and 1
    live rows) gained 21 % and 3.9 % with it (PERF.md section 6, PR
    34)."""
    if not _one_row_a_token(rows.shape[0], num_tokens, k):
        return sum_rows_by_token(rows, scale, mine // k, n_live, num_tokens,
                                 impl)
    row_of_token = jnp.argsort(mine)
    wide = rows[row_of_token].astype(jnp.float32)
    if scale is not None:
        wide = wide * scale[row_of_token][:, None]
    return jnp.where((row_of_token < n_live)[:, None], wide, 0.0)


def _rows_walked(n_live, m: int, num_tokens: int, k: int, impl: str):
    """The rows ``_sum_by_token`` visits for ``n_live`` live ones of
    ``m``: every token's where it gathers, the live blocks' where the
    kernel runs, the live rows elsewhere."""
    if _one_row_a_token(m, num_tokens, k):
        return jnp.int32(m)
    if impl == "ragged_dot":
        return n_live
    tm = _sum_rows_block(m)
    return -(-n_live // tm) * tm


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(tokens, mine, n_live, k: int, impl: str):
    """``tokens[mine // k]``: the sorted (token, choice) rows of a part
    (``mine [M]``: the pair ``b * k + c`` of every row), each a copy of
    its token. The cotangent is the live rows' (``n_live`` of them, a
    prefix) summed by token (``_sum_by_token``) where plain AD would
    scatter-add all ``M`` rows; it is rounded to the rows' dtype once,
    at the end."""
    return tokens[mine // k]


def _dispatch_fwd(tokens, mine, n_live, k, impl):
    # (an empty slice carries the tokens' count to the backward pass)
    return tokens[mine // k], (mine, n_live, tokens[:, :0])


def _dispatch_bwd(k, impl, res, g):
    mine, n_live, like_tokens = res
    d_tokens = _sum_by_token(g, None, mine, n_live, like_tokens.shape[0], k,
                             impl).astype(g.dtype)
    return d_tokens, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine(y, weight, mine, n_live, k: int, impl: str):
    """``out[b] = sum_c weight[b, c] * y[row of pair (b, c)]`` over the
    pairs that have a live row in the part: float32 ``[B, D]``, by
    ``_sum_by_token`` over the part's ``n_live`` live rows (``mine
    [M]``: the pair ``b * k + c`` of every row), each scaled by its
    pair's weight. Backward, ``d_y`` is the token's cotangent gathered
    to the rows and scaled, and the gates' ``d_w[b, c] = <y[row of (b,
    c)], g[b]>`` is the row-wise dot of ``y`` with those gathered rows
    (float32, ``[M]`` numbers) put at the rows' pairs, zero at a pair
    without a row here: it reads every row of ``y``, so the rows past
    the live ones have to be zeros (``_rows``' selects)."""
    return _sum_by_token(y, weight.reshape(-1)[mine], mine, n_live,
                         weight.shape[0], k, impl)


def _combine_fwd(y, weight, mine, n_live, k, impl):
    return _combine(y, weight, mine, n_live, k, impl), (y, weight, mine)


def _combine_bwd(k, impl, res, g):
    y, weight, mine = res
    g_rows = g[mine // k]                                        # [M, D]
    d_y = (g_rows * weight.reshape(-1)[mine][:, None]).astype(y.dtype)
    dw_row = jnp.sum(y.astype(jnp.float32) * g_rows, axis=1)     # [M]
    d_w = jnp.zeros(weight.size, jnp.float32).at[mine].set(
        dw_row, unique_indices=True).reshape(weight.shape)
    return d_y, d_w, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _live_rows(ends, lo: int, n: int):
    """How many of the sorted rows ``[lo, lo + n)`` are routed to the
    held experts: a prefix of them, the absent experts' rows sort last."""
    return jnp.clip(ends[-1] - lo, 0, n)


def _part_sizes(sizes, ends, lo: int, n: int):
    """How many of each held expert's rows lie among the sorted rows
    ``[lo, lo + n)``: the group sizes of that part's grouped products."""
    return jnp.clip(ends, lo, lo + n) - jnp.clip(ends - sizes, lo, lo + n)


def _rows(tokens, weight, w_gate, w_up, w_down, route, lo: int, n: int,
          k: int, impl: str):
    """What the held experts give for the sorted rows ``[lo, lo + n)``
    of ``route`` (``order``, the held experts' row counts and their
    running sum), summed per token: float32 ``[B, D]``. The row buffers
    hold all ``n`` rows; the first ``n_live`` of them are routed here,
    and the per-token sums (``_combine`` forward, ``_dispatch``
    backward) walk those alone. The rows past them are the absent
    experts': the products skip them and leave them unspecified, so
    they are zeroed on the way in and on the way out, forward and (by
    the same selects) backward, for the products' own cotangents and
    for the gates', which read every row."""
    order, sizes, ends = route
    mine = order[lo:lo + n]
    part = _part_sizes(sizes, ends, lo, n)
    n_live = _live_rows(ends, lo, n)
    live = (jnp.arange(n) < n_live)[:, None]

    def only_live(a):
        return jnp.where(live, a, jnp.zeros((), a.dtype))

    x = only_live(_dispatch(tokens, mine, n_live, k, impl))
    gate_act = only_live(_grouped_dot(x, w_gate, part, impl))
    up = only_live(_grouped_dot(x, w_up, part, impl))
    if lo == 0:
        # kept for the backward pass where a caller's checkpoint
        # policy says so, in place of the products that made them
        x = checkpoint_name(x, KEPT)
        gate_act = checkpoint_name(gate_act, KEPT)
        up = checkpoint_name(up, KEPT)
    h = (jax.nn.silu(gate_act.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(x.dtype)
    y = only_live(_grouped_dot(h, w_down, part, impl))
    if lo == 0:
        y = checkpoint_name(y, KEPT)
    return _combine(y, weight, mine, n_live, k, impl)


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


def sigmoid_router(tokens: jax.Array,        # [B, D]
                   router_w: jax.Array,      # [D, E]: all E experts
                   bias: jax.Array,          # [E]: the balancing biases
                   top_k: int,
                   route_norm: bool = True,
                   route_scale: float = 1.0,
                   choice: Optional[jax.Array] = None) -> SigmoidRoute:
    """The router that scores each expert by itself:
    ``s = sigmoid(tokens @ router_w)`` over all ``E`` experts in
    float32; the choice is the top-k of ``s + bias`` (``bias`` carries
    no gradient: it selects and does not weigh); the gates are ``s`` of
    the chosen, divided by their sum (``route_norm``; + 1e-20) and
    multiplied by ``route_scale``. A given ``choice`` (int ``[B, k]``)
    takes the top-k's place, gates and loads with it (a comparison
    under one routing; ``own_choice`` is still the router's).
    ``load`` is what ``balance_step`` reads."""
    E = router_w.shape[1]
    k = int(top_k)
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} must be in [1, {E}]")
    scores = jax.nn.sigmoid(tokens.astype(jnp.float32)
                            @ router_w.astype(jnp.float32))
    biased = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    own = jax.lax.top_k(biased, k)[1]                          # [B, k]
    if choice is None:
        choice = own
    top = jnp.take_along_axis(scores, choice, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    gates = top / (total + 1e-20) if route_norm else top
    load = jnp.sum(jax.nn.one_hot(choice, E, dtype=jnp.float32),
                   axis=(0, 1))
    return SigmoidRoute(choice, gates * route_scale, load, jnp.mean(total),
                        own)


def balance_step(beta: jax.Array, load: jax.Array, rate: float):
    """The balancing biases after a step that sent ``load [..., E]``
    (token, choice) pairs to each expert: every bias moves by ``rate``
    against the sign of its expert's load over its layer's mean (the
    auxiliary-loss-free rule). No gradient passes."""
    with jax.named_scope("moe"), jax.named_scope("router"):
        load = jax.lax.stop_gradient(load)
        mean = jnp.mean(load, axis=-1, keepdims=True)
        return beta - rate * jnp.sign(load - mean)


def shared_expert(tokens: jax.Array,         # [B, D]
                  w_gate: jax.Array,         # [D, F]
                  w_up: jax.Array,           # [D, F]
                  w_down: jax.Array):        # [F, D]
    """The expert every token takes: ``w_down (silu(w_gate y) * w_up
    y)`` on ALL rows, a plain product and no group of the grouped ones
    (``routed_experts``' rows, loads and drops stay the routed experts'
    alone). Computes in ``tokens.dtype``, the activation in float32 as
    a routed expert's."""
    dt = tokens.dtype
    with jax.named_scope("shared_expert"):
        gate_act = tokens @ w_gate.astype(dt)
        up = tokens @ w_up.astype(dt)
        h = (jax.nn.silu(gate_act.astype(jnp.float32))
             * up.astype(jnp.float32)).astype(dt)
        return h @ w_down.astype(dt)


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
    0 unless the split loses rows; ``rows_walked`` the sorted rows the
    per-token sums visited, which follows ``rows_here`` and not ``B *
    k``. In each part the three products run
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
    route = (order, sizes, ends)
    operands = (tokens, weight, w_gate.astype(dt), w_up.astype(dt),
                w_down.astype(dt), route)
    out = _rows(*operands, 0, fast, k, impl)
    covered = jnp.sum(_part_sizes(sizes, ends, 0, fast))
    walked = _rows_walked(_live_rows(ends, 0, fast), fast, B, k, impl)
    if fast < B * k:
        reached = rows_here > fast
        rest = B * k - fast
        out = out + _rows_if(reached, *operands, fast, rest, k, impl)
        covered = covered + jnp.where(
            reached, jnp.sum(_part_sizes(sizes, ends, fast, rest)), 0)
        walked = walked + _rows_walked(_live_rows(ends, fast, rest), rest,
                                       B, k, impl)
    out = out.astype(dt)

    dropped = (rows_here - covered).astype(jnp.float32)
    mean_load = jnp.maximum(rows_here.astype(jnp.float32) / held, 1e-9)
    return RoutedOut(out, dropped, rows_here.astype(jnp.float32),
                     walked.astype(jnp.float32),
                     jnp.max(sizes).astype(jnp.float32) / mean_load)


def check_held(num_experts: int, experts_held: int, first_expert: int):
    """Refuses held experts ``[first_expert, first_expert +
    experts_held)`` that are not among the router's ``num_experts``."""
    if not 0 <= first_expert <= num_experts - experts_held:
        raise ValueError(
            f"experts [{first_expert}, {first_expert + experts_held}) are "
            f"not among the router's {num_experts}")


def moe_scalars(moe: RoutedOut):
    """A layer's numbers of ``routed_experts`` under the names that
    ``moe_metrics`` reads once they are stacked over the layers."""
    return {"moe_dropped": moe.dropped, "moe_rows_here": moe.rows_here,
            "moe_rows_walked": moe.rows_walked,
            "moe_load_max_over_mean": moe.load_max_over_mean}


def moe_metrics(per_layer):
    """A step's metrics of its ``routed_experts`` layers from their
    ``moe_scalars`` stacked over them: the most rows any layer dropped,
    the layers' mean of the others."""
    return {"moe_dropped": jnp.max(per_layer["moe_dropped"]),
            "moe_rows_here": jnp.mean(per_layer["moe_rows_here"]),
            "moe_rows_walked": jnp.mean(per_layer["moe_rows_walked"]),
            "moe_load_max_over_mean":
                jnp.mean(per_layer["moe_load_max_over_mean"])}


# the gauges (``core/engine.Model(gauges=...)``) of ``moe_metrics``
GAUGES = {"moe.dropped": ("moe_dropped", "max"),
          "moe.rows_here": "moe_rows_here",
          "moe.rows_walked": "moe_rows_walked",
          "moe.load_max_over_mean": "moe_load_max_over_mean"}
