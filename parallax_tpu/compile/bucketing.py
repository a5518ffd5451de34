"""Batch-shape bucketing: pad ragged batches onto a fixed signature set.

Every distinct batch-shape signature a jitted step sees costs a full
XLA compile. A training stream is ragged in practice — the final
partial batch of ``run_iter``, a data pipeline that rebatches, an eval
loop with a leftover tail — and each ragged size silently retraces the
whole step while the loop looks healthy (the ``engine.recompiles``
counter). Bucketing bounds the signature set: every batch is padded up
to the smallest declared bucket size that fits, and a per-example
weight mask is threaded into the loss so the padded tail contributes
nothing.

Mask contract (``ParallaxConfig.bucket_mask_feed``, default ``"w"``):

* when the feed already exists (the lm1b ``"w"`` per-token weights,
  any per-example weight array), its padded rows are **zeroed** — a
  loss normalized by the weight sum (``sum(loss*w)/sum(w)``) is then
  exactly the unpadded batch's loss;
* when the feed is absent, a fresh ``[bucket]`` float32 mask (ones for
  real rows, zeros for padding) is **added** under that name on every
  batch — including full ones, so the feed-dict structure (and thus
  the jit signature) stays stable. Models that want loss-exact padded
  tails consume it; models that ignore it still stop recompiling but
  average the padded rows into the loss.

Full batches (size already a bucket) pass through **unmodified** when
the mask feed exists — bit-identical to the unbucketed path. Padding
replicates the last real example (edge mode) rather than writing
zeros: a zero-stuffed example can produce NaN/inf inside the loss
(log(0), division), and ``0 * nan`` is ``nan`` — edge rows are always
finite for finite data and their masked contribution is exactly zero.

Batches larger than every declared bucket pass through unchanged (one
warning): they keep their own signature, exactly as without bucketing.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from parallax_tpu.common.lib import parallax_log

BucketsArg = Union[None, str, Sequence[int]]

_warned_oversize: set = set()

# str(dtype) costs microseconds (numpy builds the name anew on every
# call) and batch_signature runs on every step's dispatch: each
# distinct dtype's name is built once
_dtype_names: Dict[np.dtype, str] = {}


def _dtype_name(dtype) -> str:
    name = _dtype_names.get(dtype)
    if name is None:
        name = _dtype_names[dtype] = str(dtype)
    return name


def resolve_buckets(shape_buckets: BucketsArg, example_batch_dim: int,
                    local_divisor: int = 1) -> Optional[Tuple[int, ...]]:
    """Validate ``Config.shape_buckets`` into an ascending size tuple.

    ``"auto"`` resolves to the example batch's leading dim — the common
    "fixed batch size with a ragged tail" stream then maps every batch
    onto one signature. Every bucket must divide evenly over the local
    devices (``local_divisor``), the same requirement ``shard_batch``
    enforces per batch — validating here turns a mid-run placement
    error into a build-time one.
    """
    if shape_buckets is None:
        return None
    if isinstance(shape_buckets, str):
        if shape_buckets != "auto":
            raise ValueError(
                f"shape_buckets must be 'auto' or a sequence of batch "
                f"sizes, got {shape_buckets!r}")
        buckets = (int(example_batch_dim),)
    else:
        buckets = tuple(sorted({int(b) for b in shape_buckets}))
        if not buckets or any(b < 1 for b in buckets):
            raise ValueError(
                f"shape_buckets must be positive batch sizes, got "
                f"{shape_buckets!r}")
    bad = [b for b in buckets if b % local_divisor != 0]
    if bad:
        raise ValueError(
            f"shape_buckets {bad} not divisible by the {local_divisor} "
            f"local device(s); every bucketed batch must still shard "
            f"evenly on dim 0")
    return buckets


def _leading_dim(batch: Dict) -> Optional[int]:
    for v in batch.values():
        shape = np.shape(v)
        if len(shape) >= 1:
            return int(shape[0])
    return None


def bucket_batch(batch: Dict, buckets: Sequence[int],
                 mask_feed: str = "w") -> Tuple[Dict, Optional[int]]:
    """Pad ``batch`` up to its bucket; returns ``(batch, bucket)``.

    ``bucket`` is None when no declared bucket fits (the batch passes
    through unchanged, keeping its own signature). Feeds whose leading
    dim differs from the batch dim (scalars, constants) pass through
    untouched. See the module docstring for the mask contract.
    """
    B = _leading_dim(batch)
    if B is None:
        return batch, None
    if B == 0:
        # padding an empty batch would mix 0-row data feeds with a
        # bucket-row mask (np.repeat of zero rows pads nothing) — an
        # empty batch is an upstream bug; fail at the source
        raise ValueError(
            "bucket_batch got an empty batch (leading dim 0); fix the "
            "producing iterator (e.g. a drop-last off-by-one)")
    bucket = next((b for b in buckets if b >= B), None)
    if bucket is None:
        key = (B, tuple(buckets))
        if key not in _warned_oversize:
            _warned_oversize.add(key)
            parallax_log.warning(
                "batch size %d exceeds every shape bucket %s; passing "
                "through unbucketed (this size keeps its own compiled "
                "signature — add a larger bucket to cover it)", B,
                tuple(buckets))
        if mask_feed not in batch:
            # keep the feed STRUCTURE stable even off-bucket: a model
            # consuming the added mask must not KeyError on an
            # oversize batch
            batch = dict(batch)
            batch[mask_feed] = np.ones((B,), np.float32)
        return batch, None
    pad = bucket - B
    if pad and mask_feed in batch \
            and np.shape(batch[mask_feed])[:1] != (B,):
        # a mask feed the pad loop below cannot zero would silently
        # train the padded rows at full weight — refuse loudly
        raise ValueError(
            f"bucket_mask_feed {mask_feed!r} has shape "
            f"{np.shape(batch[mask_feed])} whose leading dim is not "
            f"the batch dim ({B}); its padded rows cannot be zeroed. "
            f"Feed a [batch, ...]-leading weight array (or set "
            f"bucket_mask_feed to an unused name to get a fresh "
            f"[bucket] mask)")
    if pad == 0 and mask_feed in batch:
        return batch, bucket  # bit-identical fast path
    out = {}
    for name, v in batch.items():
        a = np.asarray(v)
        if pad and a.ndim >= 1 and a.shape[0] == B:
            a = np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
            if name == mask_feed:
                a[B:] = 0  # concat result is fresh: safe to write
        out[name] = a
    if mask_feed not in out:
        mask = np.ones((bucket,), np.float32)
        mask[B:] = 0.0
        out[mask_feed] = mask
    return out, bucket


def length_bucket(n: int, buckets: Sequence[int]) -> Optional[int]:
    """Smallest declared bucket >= ``n`` (None when nothing fits) —
    the length analogue of ``bucket_batch``'s batch-dim rule, used by
    the serving layer to pad ragged per-request sequence dims onto a
    bounded signature set."""
    return next((int(b) for b in sorted(buckets) if b >= n), None)


def pad_axis0(a: np.ndarray, target: int, pad_value=0) -> np.ndarray:
    """Pad ``a`` along axis 0 up to ``target`` rows with ``pad_value``
    (unlike the batch-dim edge padding, sequence padding uses an
    explicit pad token/value: models mask it via their own pad
    semantics, e.g. NMT's PAD_ID -> src_valid). No-op when already
    there; refuses to truncate."""
    a = np.asarray(a)
    n = a.shape[0]
    if n == target:
        return a
    if n > target:
        raise ValueError(
            f"pad_axis0 cannot truncate: array has {n} rows, target "
            f"{target}")
    pad = np.full((target - n,) + a.shape[1:], pad_value, a.dtype)
    return np.concatenate([a, pad], axis=0)


def batch_signature(batch) -> Tuple:
    """The batch's shape/dtype signature — the jit retrace key.

    Works on host feed dicts, placed device batches, and dicts of
    ``ShapeDtypeStruct`` alike. ``sorted``: jit's cache keys on the
    sorted flattened pytree, so feed-dict insertion order must not
    fake a distinct signature.
    """
    try:
        return tuple(sorted(
            [(k, tuple(v.shape), _dtype_name(v.dtype))
             for k, v in batch.items()]))
    except AttributeError:
        import jax

        from parallax_tpu.core import classify

        def leaf_dtype(leaf):
            # attribute first: np.asarray on a placed (multi-host:
            # non-addressable) jax.Array would force a device sync —
            # or raise — on the dispatch path
            d = getattr(leaf, "dtype", None)
            return d if d is not None else np.asarray(leaf).dtype

        return tuple(
            (classify._pathname(kp), tuple(np.shape(leaf)),
             _dtype_name(leaf_dtype(leaf)))
            for kp, leaf in
            jax.tree_util.tree_flatten_with_path(batch)[0])


def bucket_shape(shape: Tuple[int, ...], example_batch_dim: int,
                 b: int, process_scale: int = 1) -> Tuple[int, ...]:
    """The global post-placement shape of one feed leaf under bucket
    ``b``: batch-leading dims re-size to the bucket; every leading dim
    scales by ``process_scale`` — the number of processes the feed's
    dim-0 placement spans (multi-host placement assembles global
    arrays from process-local feeds; a replicated override feed spans
    1). The ONE shape rule shared by warmup aval construction
    (``Engine._bucket_avals``) and expected-signature pre-registration
    (``bucket_signatures``) — the two must agree or pre-registered
    signatures never match real steps."""
    if len(shape) >= 1 and shape[0] == example_batch_dim:
        return (b * process_scale,) + tuple(shape[1:])
    if len(shape) >= 1 and process_scale > 1:
        return (shape[0] * process_scale,) + tuple(shape[1:])
    return tuple(shape)


def bucket_signatures(batch_shapes: Dict, example_batch_dim: int,
                      buckets: Sequence[int],
                      process_scale=1) -> List[Tuple]:
    """The signature each declared bucket will present post-placement.

    ``batch_shapes`` is the (bucketed) example batch's shape tree;
    leaves re-size per bucket under the shared ``bucket_shape`` rule.
    ``process_scale``: an int, or a callable ``name -> int`` for
    per-feed spans (``Engine._feed_process_scale`` — override feeds
    need not shard dim 0 across processes).
    """
    sigs = []
    for b in buckets:
        swapped = {
            name: _Aval(bucket_shape(
                tuple(leaf.shape), example_batch_dim, b,
                process_scale(name) if callable(process_scale)
                else process_scale), leaf.dtype)
            for name, leaf in batch_shapes.items()}
        sigs.append(batch_signature(swapped))
    return sigs


class _Aval:
    """Minimal shape/dtype carrier for signature derivation."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype
