"""TPU lowering gates for the Pallas kernels (no TPU hardware needed).

VERDICT r4 missing item 3: interpret-mode parity cannot prove the
kernels lower for a real TensorCore — and it didn't: the first
`jax.export(platforms=['tpu'])` of the flash forward failed Mosaic's
(8, 128) block-tiling rule on the [B, H, T] lse output (fixed in r5 by
the official lane-broadcast layout, see ops/pallas_attention._LANES).
These tests run the full Pallas→Mosaic lowering pipeline on CPU via
jax.export, so any block-shape/layout/unsupported-op regression fails
in CI instead of on first hardware contact. What export cannot see —
the Mosaic compiler itself (VMEM allocation, TensorCore codegen: the
paged kernel lowered here for five PRs and was refused there) and the
numbers the kernels produce — is ``chip_smoke.py``'s kernels phase.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from parallax_tpu.ops import pallas_lstm
from parallax_tpu.ops.pallas_attention import (flash_attention,
                                               flash_attention_lse)


def _export_tpu(fn, *args):
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    text = exp.mlir_module()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the module"
    return text


B, T, H, D = 2, 2048, 8, 64
_S = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)


def test_flash_attention_fwd_lowers_for_tpu():
    _export_tpu(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False), _S, _S, _S)


def test_flash_attention_bwd_lowers_for_tpu():
    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    text = _export_tpu(fwd_bwd, _S, _S, _S)
    # the forward kernel and the one backward kernel (dq, dk, dv)
    assert text.count("tpu_custom_call") == 2, text.count(
        "tpu_custom_call")


def test_flash_attention_lse_bwd_lowers_for_tpu():
    """The ring-attention block surface: (out, lse) forward and the
    delta-shifted backward (lse cotangent) must lower too."""
    def fwd_bwd(q, k, v):
        def loss(*a):
            out, lse = flash_attention_lse(*a, causal=True,
                                           interpret=False)
            return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _export_tpu(fwd_bwd, _S, _S, _S)


def test_flash_attention_masked_bwd_lowers_for_tpu():
    """The kv_mask (padding) path NMT/BERT use — its [B, Tk] block
    spec violated the same tiling rule as lse before r5 reshaped it to
    [B, 1, Tk] (r5 review finding)."""
    mask = jax.ShapeDtypeStruct((B, T), jnp.int32)

    def fwd_bwd(q, k, v, m):
        return jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, kv_mask=m, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)
    text = _export_tpu(fwd_bwd, _S, _S, _S, mask)
    assert text.count("tpu_custom_call") == 2


def test_pallas_lstm_flagship_lowers_for_tpu():
    """The flagship recurrence at its real weight shape (bf16
    [1024, 8192]) through the r5 hoisted/resident kernel."""
    T_, B_ = 4, 128
    E, H_, P = 512, 2048, 512
    args = (jax.ShapeDtypeStruct((T_, B_, E), jnp.bfloat16),
            jax.ShapeDtypeStruct((E + P, 4 * H_), jnp.bfloat16),
            jax.ShapeDtypeStruct((4 * H_,), jnp.bfloat16),
            jax.ShapeDtypeStruct((H_, P), jnp.bfloat16))
    _export_tpu(lambda x, w, b, wp: pallas_lstm.lstm_scan(
        x, w, b, wp, impl="pallas", interpret=False), *args)


def test_pallas_lstm_bwd_lowers_for_tpu():
    """ISSUE 14: the time-reversed backward kernel at the flagship
    shape — residual-saving forward + backward recurrence both lower
    through Mosaic (reversed/clamped index maps, resident transposed
    matmuls, fp32 carry scratch). Exactly two custom calls: the
    hoisted/epilogue matmuls are plain XLA by design."""
    T_, B_ = 4, 128
    E, H_, P = 512, 2048, 512
    args = (jax.ShapeDtypeStruct((T_, B_, E), jnp.bfloat16),
            jax.ShapeDtypeStruct((E + P, 4 * H_), jnp.bfloat16),
            jax.ShapeDtypeStruct((4 * H_,), jnp.bfloat16),
            jax.ShapeDtypeStruct((H_, P), jnp.bfloat16))

    def fwd_bwd(x, w, b, wp):
        return jax.grad(lambda *a: jnp.sum(pallas_lstm.lstm_scan(
            *a, impl="pallas", bwd_impl="kernel",
            interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2, 3))(x, w, b, wp)
    text = _export_tpu(fwd_bwd, *args)
    assert text.count("tpu_custom_call") == 2, text.count(
        "tpu_custom_call")


def test_pallas_lstm_under_mesh_lowers_for_tpu():
    """The training main path: the kernel forward AND backward inside
    the per-device shard_map with the VMA checker ON (compiled kernels
    keep it on; interpret mode, which every CPU test runs, turns it
    off). First traced for a TensorCore in PR 22 and refused twice —
    pallas out_shapes without ``vma``, then weight cotangents typed
    device-varying against replicated primals."""
    from parallax_tpu.core import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(jax.devices()[:8], num_partitions=4)
    T_, B_ = 2, 128
    E, H_, P = 64, 128, 64
    args = (jax.ShapeDtypeStruct((T_, B_, E), jnp.bfloat16),
            jax.ShapeDtypeStruct((E + P, 4 * H_), jnp.bfloat16),
            jax.ShapeDtypeStruct((4 * H_,), jnp.bfloat16),
            jax.ShapeDtypeStruct((H_, P), jnp.bfloat16))

    def fwd_bwd(x, w, b, wp):
        return jax.grad(lambda *a: jnp.sum(pallas_lstm.lstm_scan(
            *a, impl="pallas", bwd_impl="kernel", interpret=False,
            mesh=mesh, batch_axes=mesh_lib.BATCH_AXES
        ).astype(jnp.float32)), argnums=(0, 1, 2, 3))(x, w, b, wp)
    text = _export_tpu(fwd_bwd, *args)
    assert text.count("tpu_custom_call") == 2, text.count(
        "tpu_custom_call")
    # the weight-gradient reduction over the batch axes is in the
    # program (the transpose of the weights' cast to varying)
    assert "all_reduce" in text or "all-reduce" in text


def test_pallas_lstm_recompute_fallback_lowers_for_tpu():
    """The refusal/size-guard fallback must stay TPU-lowerable too:
    forced recompute keeps ONE custom call (the primal-only forward —
    no residual streams; value_and_grad keeps the primal live, grad
    alone would DCE the forward) next to the pure-XLA transposed
    scan."""
    T_, B_ = 4, 128
    E, H_, P = 512, 2048, 512
    args = (jax.ShapeDtypeStruct((T_, B_, E), jnp.bfloat16),
            jax.ShapeDtypeStruct((E + P, 4 * H_), jnp.bfloat16),
            jax.ShapeDtypeStruct((4 * H_,), jnp.bfloat16),
            jax.ShapeDtypeStruct((H_, P), jnp.bfloat16))

    def fwd_bwd(x, w, b, wp):
        return jax.value_and_grad(
            lambda *a: jnp.sum(pallas_lstm.lstm_scan(
                *a, impl="pallas", bwd_impl="recompute",
                interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2, 3))(x, w, b, wp)
    text = _export_tpu(fwd_bwd, *args)
    assert text.count("tpu_custom_call") == 1, text.count(
        "tpu_custom_call")


@pytest.mark.parametrize("slots", [2560, 10752, 18432])
def test_adagrad_rows_lowers_for_tpu(slots):
    """ISSUE 26: SliceAdagrad's in-place row update at the benchmark
    cells' shapes (f32[793470, 512] table and accumulator left in HBM
    and aliased to the outputs, the id list scalar-prefetched, manual
    DMAs of 8-row groups). The table's 793,470 rows are not a multiple
    of 8, so the partial last group goes through the scatter beside
    the ONE custom call."""
    from parallax_tpu.ops import sparse_optim as so

    V, D = 793470, 512
    table = jax.ShapeDtypeStruct((V, D), jnp.float32)
    sl = so.SliceAdagrad(0.2)

    def rows(param, acc, uids, gsum):
        with pytest.MonkeyPatch.context() as m:
            # off the chip the kernel would interpret itself
            m.setattr(so, "adagrad_rows", functools.partial(
                so.adagrad_rows, interpret=False))
            return sl._kernel_rows(param, acc, uids, gsum)
    text = _export_tpu(rows, table, table,
                       jax.ShapeDtypeStruct((slots,), jnp.int32),
                       jax.ShapeDtypeStruct((slots, D), jnp.float32))
    assert text.count("tpu_custom_call") == 1, text.count(
        "tpu_custom_call")
    assert "output_operand_aliases" in text      # in place: no [V, D] copy


@pytest.mark.parametrize("V, D", [(25024, 2048), (12288, 2304),
                                  (12544, 3840), (12547, 3840)])
def test_adam_rows_lowers_for_tpu(V, D):
    """ISSUE 40: SliceAdam's in-place row update at the Adam cells'
    tables (Trinity-Mini's and Keye's 2,048 wide, Mellum2's 2,304,
    Olmo-Hybrid's 3,840) and 8,192 slots: param, m and v left in HBM
    and aliased to the outputs, the bias corrections scalar-prefetched
    beside the ids, ONE custom call; a table whose rows are not a
    multiple of 8 takes the partial last group through the scatter."""
    from parallax_tpu.ops import sparse_optim as so

    table = jax.ShapeDtypeStruct((V, D), jnp.float32)
    sl = so.SliceAdam(3e-4)

    def rows(param, m, v, uids, gsum, corr):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(so, "adam_rows", functools.partial(
                so.adam_rows, interpret=False))
            return sl._kernel_rows(param, m, v, uids, gsum, corr)
    text = _export_tpu(rows, table, table, table,
                       jax.ShapeDtypeStruct((8192,), jnp.int32),
                       jax.ShapeDtypeStruct((8192, D), jnp.float32),
                       jax.ShapeDtypeStruct((2,), jnp.float32))
    assert text.count("tpu_custom_call") == 1, text.count(
        "tpu_custom_call")
    assert "output_operand_aliases" in text
    assert "adam_rows" in text


def test_paged_attention_kernel_lowers_for_tpu():
    """ISSUE 16: the fused paged-attention decode kernel at the
    flagship decode shape (bf16, 2048-cap 128-token pages, spec-verify
    width 3) — scalar-prefetch page-table index maps, equal-dims K/V
    page blocks, (H, G, LANES) softmax scratch all lower through
    Mosaic. Exactly ONE custom call: the whole page sweep is a single
    kernel, never one call per page."""
    from parallax_tpu.ops import pallas_paged_attention as ppa

    F = ppa.FLAGSHIP_DECODE
    args = (jax.ShapeDtypeStruct((F["S"], F["G"], F["D"]),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((F["pool_pages"], F["page_size"],
                                  F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["pool_pages"], F["page_size"],
                                  F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["S"], F["P"]), jnp.int32),
            jax.ShapeDtypeStruct((F["S"], F["G"]), jnp.int32))
    text = _export_tpu(
        lambda q, kp, vp, pages, pos: ppa.paged_decode_attention(
            q, kp, vp, pages, pos, num_heads=F["num_heads"],
            page_size=F["page_size"], impl="kernel",
            interpret=False), *args)
    assert text.count("tpu_custom_call") == 1, text.count(
        "tpu_custom_call")


def test_paged_attention_single_token_lowers_for_tpu():
    """The plain (non-speculative) decode step is G=1 — a different
    block shape for q/out and the softmax scratch; it must lower on
    its own, not just at the verify width."""
    from parallax_tpu.ops import pallas_paged_attention as ppa

    F = ppa.FLAGSHIP_DECODE
    args = (jax.ShapeDtypeStruct((F["S"], 1, F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["pool_pages"], F["page_size"],
                                  F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["pool_pages"], F["page_size"],
                                  F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["S"], F["P"]), jnp.int32),
            jax.ShapeDtypeStruct((F["S"], 1), jnp.int32))
    text = _export_tpu(
        lambda q, kp, vp, pages, pos: ppa.paged_decode_attention(
            q, kp, vp, pages, pos, num_heads=F["num_heads"],
            page_size=F["page_size"], impl="kernel",
            interpret=False), *args)
    assert text.count("tpu_custom_call") == 1, text.count(
        "tpu_custom_call")


def test_paged_attention_einsum_fallback_has_no_custom_call():
    """The einsum executor is the refusal/off-TPU fallback — it must
    stay pure XLA (zero Mosaic kernels) so 'einsum' really means 'no
    Pallas in the program'."""
    from parallax_tpu.ops import pallas_paged_attention as ppa

    F = ppa.FLAGSHIP_DECODE
    args = (jax.ShapeDtypeStruct((F["S"], F["G"], F["D"]),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((F["pool_pages"], F["page_size"],
                                  F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["pool_pages"], F["page_size"],
                                  F["D"]), jnp.bfloat16),
            jax.ShapeDtypeStruct((F["S"], F["P"]), jnp.int32),
            jax.ShapeDtypeStruct((F["S"], F["G"]), jnp.int32))
    exp = jax.export.export(jax.jit(
        lambda q, kp, vp, pages, pos: ppa.paged_decode_attention(
            q, kp, vp, pages, pos, num_heads=F["num_heads"],
            page_size=F["page_size"], impl="einsum")),
        platforms=["tpu"])(*args)
    assert exp.mlir_module().count("tpu_custom_call") == 0


def test_hybrid_engine_step_lowers_for_tpu():
    """The WHOLE flagship-path training step — hybrid plan, slices
    sparse grads, 8-device (repl x shard) mesh — lowers for a TPU
    target, GSPMD collectives included. This is the engine-level
    companion to the kernel gates above: a sharding/layout construct
    with no TPU lowering would fail here before first hardware
    contact."""
    import numpy as np
    from parallax_tpu.common.config import ParallaxConfig
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import lm1b

    devices = jax.devices()[:8]
    mesh = mesh_lib.build_mesh(devices, num_partitions=4)
    cfg = lm1b.tiny_config(num_partitions=4, sparse_grad_mode="slices")
    config = ParallaxConfig(run_option="HYBRID", search_partitions=False,
                            sparse_grad_mode="slices")
    batch = lm1b.make_batch(np.random.default_rng(0), 8, 4,
                            cfg.vocab_size)
    eng = engine_lib.Engine(lm1b.build_model(cfg), mesh, config, batch)
    state = eng.init_state(0)
    exp = jax.export.export(eng._step_jit, platforms=["tpu"])(
        state, eng.shard_batch(batch))
    text = exp.mlir_module()
    n_coll = (text.count("all_gather") + text.count("all_reduce")
              + text.count("reduce_scatter") + text.count("all_to_all"))
    assert n_coll > 0, "no collectives in the sharded step module"


def test_tp_sp_engine_step_lowers_for_tpu():
    """And the TP x SP composition (Megatron kernels, seq-sharded
    resting activations, vocab-parallel head) on the same mesh."""
    import numpy as np
    from parallax_tpu.common.config import ParallaxConfig
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import long_context as lc

    mesh = mesh_lib.build_mesh(jax.devices()[:8], num_partitions=4)
    config = ParallaxConfig(run_option="HYBRID", search_partitions=False)
    cfg = lc.tiny_config(max_len=16, num_heads=4)
    cfg.parallelism = "tensor"
    cfg.tp_sequence_parallel = True
    batch = lc.make_batch(np.random.default_rng(3), batch_size=16,
                          seq_len=16, vocab_size=cfg.vocab_size)
    eng = engine_lib.Engine(lc.build_model(cfg), mesh, config, batch)
    state = eng.init_state(0)
    exp = jax.export.export(eng._step_jit, platforms=["tpu"])(
        state, eng.shard_batch(batch))
    assert len(exp.mlir_module()) > 0
