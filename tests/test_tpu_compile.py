"""The Mosaic kernels of the Keye-VL-2.0, ZAYA1-8B, Mellum2,
Olmo-Hybrid and Trinity-Mini steps (and the last four's whole steps, for
their peak memory), compiled at the published
widths by the TPU's own compiler against a described v5e (no chip is
attached, nothing runs): what interpret mode cannot show: a
block that is not aligned to the tiling, more VMEM than a kernel may
take, a transposed product Mosaic refuses.

The topology is described inside a fixture of this one file, never at
import (the on-chip-measurement guide, section 2): every xdist worker
collects the same tests, and only the worker that is handed this file
loads the TPU's library.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


def _kernels(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _outside_fusions(text, shape):
    """The instructions of shape ``shape`` that stand outside every
    fused computation: what a fusion or a custom call writes to or reads
    from memory (an instruction inside a fusion lives in registers)."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", text))
    hits, name = [], None
    for line in text.splitlines():
        start = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if start:
            name = start.group(1)
        elif name not in fused and shape in line:
            hits.append(line.strip())
    return hits


@pytest.mark.parametrize("keys", [2048, 8192])
def test_sparse_attention_kernels_compile_at_published_widths(one_chip, keys):
    """One 512-query chunk of 32 query heads on 4 key/value heads of 128
    against 2,048 keys, and against 8,192 (the longest band): forward,
    the heads' summed probabilities, the backward kernel, which hands
    back the indexer's target too, and the indexer's own backward
    kernel, which keeps the chunk's per-head products ``[512, 16,
    keys]`` out of memory. The summed probabilities are the forward's
    alone: the chunk's backward, compiled by itself, holds no
    ``sparse_attn_probs``; its target leaves ``sparse_attn_bwd``."""
    from parallax_tpu.ops import sparse_attention as sa

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, qi, ki, wi, start):
        out, kl, _, _ = sa._chunk(q, k, v, qi, ki, wi, start,
                                  jnp.int32(2048), "kernel")
        return jnp.sum(out.astype(jnp.float32)) + kl

    def bwd(q, k, v, qi, ki, wi, start, sel, out, lse, d_out):
        return sa._chunk_bwd("kernel", (q, k, v, qi, ki, wi, start,
                                        (sel, out, lse)),
                             (d_out, jnp.float32(1)))

    inputs = (sds((1, 4, 8, 512, 128)), sds((1, 4, keys, 128)),
              sds((1, 4, keys, 128)), sds((1, 512, 16, 64)),
              sds((1, keys, 64)), sds((1, 512, 16)), sds((), jnp.int32))
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5)),
                    *inputs).as_text()
    # forward, the summed probabilities (for the loss), backward (and
    # the target again, for the loss's gradient), the indexer's backward
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for name in ("sparse_attn_fwd", "sparse_attn_bwd", "sparse_attn_probs",
                 "indexer_bwd"):
        assert name in text
    # the forward's scores are one fusion down to [512, keys]; nothing
    # else may hold a head's products of the chunk
    assert f"[512,16,{keys}]" in text
    assert _outside_fusions(text, f"[512,16,{keys}]") == []

    forward = _compile(lambda *a: sa._chunk(*a, jnp.int32(2048), "kernel"),
                       *inputs)
    assert _kernels(forward) == 2
    assert "sparse_attn_probs" in forward.as_text()
    backward = _compile(bwd, *inputs, sds((1, 512, keys), jnp.bool_),
                        sds((1, 4, 8, 512, 128)),
                        sds((1, 4, 8, 512, 8), jnp.float32),
                        sds((1, 4, 8, 512, 128)))
    text = backward.as_text()
    assert _kernels(backward) == 2 and "sparse_attn_probs" not in text
    calls = _outside_fusions(text, 'custom_call_target="tpu_custom_call"')
    (call,) = [c for c in calls if "sparse_attn_bwd" in c]
    assert f"f32[1,512,{keys}]" in call


def test_sparse_attn_fwd_alone_against_the_longest_band(one_chip):
    """The forward kernel as a chunk calls it (the queries scaled, the
    selection cast to int8 before it) against 8,192 keys, the longest
    band and the largest scratch: still ONE Mosaic call named
    ``sparse_attn_fwd`` that stands outside every fusion, where
    ``benchmark/lib/kernel_calls`` finds it (a ``kCustom`` fusion
    wrapped around the call would hide it from that reader)."""
    from parallax_tpu.ops import sparse_attention as sa

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd(q, k, v, sel, q_start):
        qs, mask, start = sa._kernel_operands(q, sel, q_start)
        return sa._fwd_call(qs, k, v, mask, start, False)

    compiled = _compile(
        fwd, sds((1, 4, 8, 512, 128)), sds((1, 4, 8192, 128)),
        sds((1, 4, 8192, 128)), sds((1, 512, 8192), jnp.bool_),
        sds((), jnp.int32))
    text = compiled.as_text()
    assert _kernels(compiled) == 1
    calls = _outside_fusions(text, 'custom_call_target="tpu_custom_call"')
    assert len(calls) == 1 and "sparse_attn_fwd" in calls[0]
    # the rows' logsumexp leaves in the format the other kernels read
    assert "f32[1,4,8,512,8]" in calls[0]


@pytest.mark.parametrize("tokens,dim,width,experts,held", [
    pytest.param(2048, 2048, 768, 128, 16, id="keye"),
    pytest.param(8192, 2304, 896, 64, 16, id="mellum2"),
])
def test_routed_experts_kernels_compile_at_published_widths(
        one_chip, tokens, dim, width, experts, held):
    """Keye's experts (2,048 tokens of width 2048 through 16 held
    experts of width 768, top-8 of 128) and Mellum2's (8,192 tokens of
    width 2304, a row that is no multiple of 1,024 lanes, through 16
    held of width 896, top-8 of 64), value and gradient. The fast part
    of the rows: three grouped products and the combine's
    ``sum_rows`` forward, six grouped products and the dispatch's
    ``sum_rows`` backward. The part behind the ``cond``: the same four
    forward, and in the backward ``cond`` the three forward products
    again (their ``sum_rows`` is nobody's there) with the same seven.
    No buffer holds a row for every (token, choice) pair."""
    from parallax_tpu.ops import moe

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(toks, router, w_gate, w_up, w_down):
        route = moe.linear_router(toks, router, 8)
        return jnp.sum(moe.routed_experts(
            toks, route.choice, route.gate, w_gate, w_up, w_down,
            num_experts=experts, impl="gmm").out.astype(jnp.float32) ** 2)

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 2, 3, 4)),
        sds((tokens, dim), jnp.bfloat16), sds((dim, experts), jnp.float32),
        sds((held, dim, width), jnp.float32),
        sds((held, dim, width), jnp.float32),
        sds((held, width, dim), jnp.float32))
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert _kernels(compiled) == len(names) == 25
    assert sum("sum_rows" in n for n in names) == 4
    assert sum("gmm" in n for n in names) == 21
    # no product over tokens x experts held, no row for every pair
    assert f"[{tokens},{held},{width}]" not in text
    assert f"[{8 * tokens},{dim}]" not in text


def test_flash_kernels_compile_with_grouped_heads_at_8k(one_chip):
    """8 query heads on 2 key/value heads of 128 against 8,192 causal
    keys in tiles of 512: the dense flash kernels' forward and their one
    backward pass Mosaic under their names, and no K or V of the query
    heads' count is in memory."""
    from parallax_tpu.ops.pallas_attention import flash_attention

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, q_tile=512, block_k=512,
            interpret=False).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(1, 8192, 8, 128), sds(1, 8192, 2, 128),
                        sds(1, 8192, 2, 128))
    text = compiled.as_text()
    assert _kernels(compiled) == 2
    calls = _outside_fusions(text, 'custom_call_target="tpu_custom_call"')
    assert [next(n for n in ("flash_fwd", "flash_bwd")
                 if n in c.split(" = ")[0]) for c in calls] \
        == ["flash_fwd", "flash_bwd"]
    # dq leaves at the query heads' count, dk and dv at the key/value
    # heads'
    assert "bf16[1,8,8192,128]" in calls[1]
    assert "bf16[1,2,8192,128]" in calls[1]


@pytest.mark.parametrize("tiles", [(512, 512), (256, 256)])
def test_windowed_flash_kernels_compile_at_mellum2s_heads(one_chip, tiles):
    """32 query heads on 4 key/value heads of 128 against 8,192 keys
    under a window of 1,024 and a traced flag: the two windowed
    kernels pass Mosaic beside the two plain ones, each kind once a
    pass under its ``conditional``."""
    from parallax_tpu.ops.pallas_attention import flash_attention

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, flag):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, q_tile=tiles[0], block_k=tiles[1],
            window=1024, window_on=flag,
            interpret=False).astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), sds(1, 8192, 32, 128),
        sds(1, 8192, 4, 128), sds(1, 8192, 4, 128),
        jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip))
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(n.rsplit(".", 1)[0] if "." in n else n for n in names) \
        == ["flash_bwd", "flash_bwd_win", "flash_fwd", "flash_fwd_win"]
    assert len(re.findall(r" conditional\(", text)) == 2


def test_windowed_flash_kernels_compile_at_trinitys_window(one_chip):
    """The same heads under Trinity-Mini's window of 2,048, four key
    tiles of 512 and not two: the two windowed kernels pass Mosaic
    beside the two plain ones under the traced flag."""
    from parallax_tpu.ops.pallas_attention import flash_attention

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, flag):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, q_tile=512, block_k=512, window=2048,
            window_on=flag, interpret=False).astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), sds(1, 8192, 32, 128),
        sds(1, 8192, 4, 128), sds(1, 8192, 4, 128),
        jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip))
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(n.rsplit(".", 1)[0] if "." in n else n for n in names) \
        == ["flash_bwd", "flash_bwd_win", "flash_fwd", "flash_fwd_win"]
    assert len(re.findall(r" conditional\(", text)) == 2


@pytest.mark.parametrize("heads,kv_heads,dim,window", [
    pytest.param(20, 20, 256, None, id="glm"),
    pytest.param(32, 4, 128, 1024, id="mellum2"),
])
def test_one_backward_kernel_a_kind_within_the_vmem_limit(
        one_chip, heads, kv_heads, dim, window):
    """The backward alone, from the forward's kept output and logsumexp,
    at GLM-4.7-Flash's latent heads (20 on 20 of 256) and at Mellum2's
    window (32 on 4 of 128, a group of 8, a traced flag) over 8,192
    causal keys in tiles of 512: ONE Mosaic call a kind of layer,
    ``flash_bwd`` (and ``flash_bwd_win``), each making dq, dk and dv,
    and none asking for or using more than 64 MiB of scoped VMEM (a
    kernel that asked for 100 MiB cost a neighbour its place in VMEM,
    ``PERF.md`` section 7)."""
    from parallax_tpu.ops import pallas_attention as pa

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def bwd(q, k, v, out, lse, g, flag):
        return pa._flash_backward(
            q, k, v, None, out, lse, g, True, dim ** -0.5, 512, 512, False,
            window=window, window_on=None if window is None else flag)

    q = sds(1, heads, 8192, dim)
    kv = sds(1, kv_heads, 8192, dim)
    text = _compile(bwd, q, kv, kv, q, sds(1, heads, 8192,
                                           dtype=jnp.float32),
                    q, sds(dtype=jnp.bool_)).as_text()
    calls = _outside_fusions(text, 'custom_call_target="tpu_custom_call"')
    names = [c.split(" = ")[0].split("%")[-1].split(".")[0] for c in calls]
    assert sorted(names) == (["flash_bwd"] if window is None
                             else ["flash_bwd", "flash_bwd_win"])
    mib = 1024 * 1024
    for call in calls:
        (asked,) = re.findall(
            r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call)
        (used,) = re.findall(
            r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', call)
        assert int(asked) <= 64 * mib and 0 < int(used) <= 64 * mib
        assert f"bf16[1,{kv_heads},8192,{dim}]" in call


def test_zaya_step_compiles_at_published_widths_and_fits(topo):
    """ZAYA1-8B's training step as the benchmark's cell runs it (6
    layers, 8 of 16 experts, 32,784 rows, one sequence of 8,192; every
    width as published) through ``Engine`` for the described v5e: the
    flash and grouped-product kernels pass Mosaic inside the scan's
    body, the tied table is the dense group's, and the executable's
    peak (``peak_memory_in_bytes``: parameters, moments and the
    compiler's temporaries) is under 15.5 GB of the chip's 16.9."""
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import zaya

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    cfg = zaya.ZayaConfig(vocab_size=32784, num_layers=6, experts_held=8,
                          warmup_steps=1000, num_partitions=1)
    model = zaya.build_model(cfg, impls=("flash", "gmm"))
    mesh = mesh_lib.build_mesh(devices=[dev], num_partitions=1)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in zaya.make_batch(np.random.default_rng(0), 1,
                                         cfg.seq_len, cfg.vocab_size).items()}
    engine = engine_lib.Engine(model, mesh,
                               parallax.Config(run_option="HYBRID"), batch)
    spec = engine.plan.var_specs["emb"]
    assert not spec.is_sparse
    assert spec.reason == "gathered but also used densely"
    state = jax.eval_shape(engine._init_jit,
                           jax.ShapeDtypeStruct((), jnp.int32))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    with mesh:
        compiled = engine._step_jit.trace(on_chip(state), on_chip(batch)) \
            .lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    peak = memory.peak_memory_in_bytes
    print(f"zaya1-8b step: peak_memory_in_bytes {peak / 1e9:.2f} GB "
          f"(arguments {memory.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f})")
    params = sum(int(np.prod(s.shape))
                 for s in jax.tree.leaves(state.params))
    assert params == pytest.approx(708e6, rel=2e-3)
    assert 4.23e9 < peak < 15.5e9
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    kinds = sorted({n.rsplit(".", 1)[0] for n in names})
    # (no `sum_rows`: one choice a token and every pair in one part, so
    # the per-token sums are gathers of each token's own row)
    assert kinds == ["flash_bwd", "flash_fwd", "gmm", "tgmm"]
    # neither every expert for every token nor whole float32 scores
    assert "[8192,8,2048]" not in text
    assert not re.search(r"f32\[(1,)?8192,8192\]", text)


def test_mellum2_step_compiles_at_published_widths_and_fits(topo):
    """Mellum2-12B-A2.5B's training step as the benchmark's cell runs it
    (one period of 4 layers, 16 of 64 experts, 12,288 rows, one sequence
    of 8,192; every width as published) through ``Engine`` for the
    described v5e: 538.5 M parameters, a peak
    (``peak_memory_in_bytes``) between the driver's floor and 15.5 GB
    of the chip's 16.9, ONE loop over the layers in each direction, and
    in them both kinds' flash kernels once each: the plain and the
    windowed forward call in the forward loop's body, the two backward
    calls in the backward loop's, no forward kernel made again by the
    rematerialisation."""
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import mellum2

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    cfg = mellum2.Mellum2Config(vocab_size=12288, num_layers=4,
                                experts_held=16, warmup_steps=20000,
                                num_partitions=1)
    model = mellum2.build_model(cfg, impls=("flash", "gmm"))
    mesh = mesh_lib.build_mesh(devices=[dev], num_partitions=1)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in mellum2.make_batch(
                 np.random.default_rng(0), 1, cfg.seq_len,
                 cfg.vocab_size).items()}
    engine = engine_lib.Engine(
        model, mesh, parallax.Config(run_option="HYBRID",
                                     sparse_grad_mode="slices"), batch)
    assert engine.plan.var_specs["emb"].is_sparse
    state = jax.eval_shape(engine._init_jit,
                           jax.ShapeDtypeStruct((), jnp.int32))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    with mesh:
        compiled = engine._step_jit.trace(on_chip(state), on_chip(batch)) \
            .lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    peak = memory.peak_memory_in_bytes
    print(f"mellum2-12b-a2.5b step: peak_memory_in_bytes {peak / 1e9:.2f} "
          f"GB (arguments {memory.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f})")
    params = sum(int(np.prod(s.shape))
                 for s in jax.tree.leaves(state.params))
    assert params == pytest.approx(538.5e6, rel=1e-3)
    assert 4.23e9 < peak < 15.5e9
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    flash = sorted(n for n in names if n.startswith("flash_"))
    # each of the four once in the whole program: the loops' bodies are
    # traced once, and the kept output and logsumexp spare the backward
    # pass a second forward call
    assert [n.rsplit(".", 1)[0] if "." in n else n for n in flash] == [
        "flash_bwd", "flash_bwd_win", "flash_fwd", "flash_fwd_win"]
    # (the experts' second part, under its own `cond`, names its calls
    # after the transformation that made them)
    assert all("gmm" in n or "sum_rows" in n for n in names
               if not n.startswith("flash_"))
    assert any("sum_rows" in n for n in names)
    # ONE loop over the layers a direction: the entry computation holds
    # two, the forward one's body the `conditional` with the two forward
    # calls and the backward one's the two backward calls
    entry = text[text.index("\nENTRY "):]
    bodies = re.findall(r" while\([^\n]*body=%([\w.\-]+)", entry)
    assert len(bodies) == 2

    def computation(name):
        start = text.index(f"\n%{name} (")
        return text[start:text.index("\n}\n", start)]

    def calls_under_conditionals(body):
        found = []
        for line in computation(body).splitlines():
            if " conditional(" in line:
                branches = re.search(r"branch_computations=\{([^}]*)\}",
                                     line).group(1)
                for branch in re.findall(r"%([\w.\-]+)", branches):
                    found += re.findall(r"%(flash_\w+)\.\d+ = ",
                                        computation(branch))
        return sorted(found)

    assert calls_under_conditionals(bodies[0]) == ["flash_fwd",
                                                   "flash_fwd_win"]
    assert calls_under_conditionals(bodies[1]) == ["flash_bwd",
                                                   "flash_bwd_win"]
    # neither every expert for every token, nor a row for every (token,
    # choice) pair, nor whole float32 scores
    assert "[8192,16,896]" not in text
    assert "[65536,2304]" not in text
    assert not re.search(r"f32\[(1,)?8192,8192\]", text)


@pytest.mark.parametrize("chunk", [64, 128])
def test_delta_rule_kernels_compile_at_olmo_hybrids_heads(one_chip, chunk):
    """The gated delta rule's forward and backward kernels at 15 heads of
    96 keys and 192 values over 8,192 tokens, bfloat16 operands and
    float32 gates: blocks of 96 and 192 lanes (no multiple of 128), the
    products transposed on their first operand, the triangular system's
    float32 products. Two calls, the forward's not made again."""
    from parallax_tpu.ops import delta_rule

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, g, beta):
        o = delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=chunk,
                                        impl="kernel")
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
        sds((1, 8192, 15, 96)), sds((1, 8192, 15, 96)),
        sds((1, 8192, 15, 192)), sds((1, 8192, 15), jnp.float32),
        sds((1, 8192, 15), jnp.float32))
    assert _kernels(compiled) == 2
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())
    # (outside a rematerialised layer the compiler names a call after
    # the transformation that made it: `jvp_delta_fwd_`; the whole step
    # below holds the plain names the benchmark's readers look for)
    assert len(names) == 2
    assert sum("delta_fwd" in n for n in names) == 1
    assert sum("delta_bwd" in n for n in names) == 1


def test_olmo_hybrid_step_compiles_at_published_widths_and_fits(
        topo, monkeypatch):
    """Olmo-Hybrid-7B's training step as the benchmark's cell runs it
    (one period of 4 layers, 15 of 30 heads, 12,544 rows, one sequence
    of 8,192; every width as published) through ``Engine`` for the
    described v5e: 766.2 M parameters, a peak (``peak_memory_in_bytes``)
    between the driver's floor and 15.6 GB of the chip's 16.9 (the
    compiler reads 15.32 GB with the MLP's up and down products kept,
    0.97 GB of them, and 14.65 without; the runtime's
    ``memory_peak_bytes`` read 12.39 GB on the chip under both: it does
    not see a program's temporaries, which the kept arrays are), each of
    the five kernels ONCE in the whole program (one body a kind of
    layer, no forward kernel made again by the rematerialisation) and of
    the MLP's products ONE a kind of layer in the rematerialised forward
    (the gate's; ``models/olmo_hybrid.MLP_KEPT``), where there were
    three; the table's lazy Adam in place by ``adam_rows``, as on the
    chip (the executor's rule reads the backend and its VMEM, the CPU's
    here: the test steers them to a v5e's; ISSUE 40)."""
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import olmo_hybrid
    from parallax_tpu.ops import sparse_optim as so

    rule = so._row_executor
    monkeypatch.setattr(so, "_row_executor",
                        lambda p, a, mesh, backend: rule(p, a, mesh, "tpu"))
    monkeypatch.setattr(so, "_vmem_bytes", lambda: 128 * 1024 * 1024)
    monkeypatch.setattr(so, "adam_rows", functools.partial(
        so.adam_rows, interpret=False))

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab_size=12544, num_layers=4, heads_held=15, warmup_steps=20000,
        num_partitions=1)
    model = olmo_hybrid.build_model(cfg, impls=("flash", "kernel"))
    mesh = mesh_lib.build_mesh(devices=[dev], num_partitions=1)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in olmo_hybrid.make_batch(
                 np.random.default_rng(0), 1, cfg.seq_len,
                 cfg.vocab_size).items()}
    engine = engine_lib.Engine(
        model, mesh, parallax.Config(run_option="HYBRID",
                                     sparse_grad_mode="slices"), batch)
    assert engine.plan.var_specs["emb"].is_sparse
    state = jax.eval_shape(engine._init_jit,
                           jax.ShapeDtypeStruct((), jnp.int32))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    with mesh:
        compiled = engine._step_jit.trace(on_chip(state), on_chip(batch)) \
            .lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    peak = memory.peak_memory_in_bytes
    print(f"olmo-hybrid-7b step: peak_memory_in_bytes {peak / 1e9:.2f} "
          f"GB (arguments {memory.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f})")
    params = sum(int(np.prod(s.shape))
                 for s in jax.tree.leaves(state.params))
    assert params == pytest.approx(766.2e6, rel=1e-3)
    assert 4.23e9 < peak < 15.6e9
    text = compiled.as_text()
    products = re.findall(r"[^\n]* convolution\([^\n]*", text)
    assert len(products) > 60       # the pattern still finds the products
    assert sum("rematted_computation/mlp" in p for p in products) == 2
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(n.split(".")[0] for n in names) == [
        "adam_rows", "delta_bwd", "delta_fwd", "flash_bwd", "flash_fwd"]
    assert not re.search(r"f32\[(1,)?8192,8192\]", text)


def test_trinity_step_compiles_at_published_widths_and_fits(topo):
    """Trinity-Mini's training step as the benchmark's cell runs it (one
    dense layer and one period of 4 expert layers, 16 of 128 experts,
    25,024 rows, one sequence of 8,192; every width as published)
    through ``Engine`` for the described v5e: 705.47 M parameters, a
    peak (``peak_memory_in_bytes``) between the driver's floor and 15.7
    GB of the chip's 16.9; the dense layer's windowed kernels called
    straight, ONE loop over the expert layers in each direction with
    both kinds' kernels under its ``conditional``s, no forward kernel
    made again by the rematerialisation."""
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import trinity

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    cfg = trinity.TrinityConfig(
        vocab_size=25024, num_layers=5, num_dense_layers=1,
        layer_types=(trinity.SLIDING,) * 4 + (trinity.FULL,),
        experts_held=16, warmup_steps=20000, num_partitions=1)
    model = trinity.build_model(cfg, impls=("flash", "gmm"))
    mesh = mesh_lib.build_mesh(devices=[dev], num_partitions=1)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in trinity.make_batch(
                 np.random.default_rng(0), 1, cfg.seq_len,
                 cfg.vocab_size).items()}
    engine = engine_lib.Engine(
        model, mesh, parallax.Config(run_option="HYBRID",
                                     sparse_grad_mode="slices"), batch)
    assert engine.plan.var_specs["emb"].is_sparse
    state = jax.eval_shape(engine._init_jit,
                           jax.ShapeDtypeStruct((), jnp.int32))
    assert state.model_state["router_bias"].shape == (4, 128)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    with mesh:
        compiled = engine._step_jit.trace(on_chip(state), on_chip(batch)) \
            .lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    peak = memory.peak_memory_in_bytes
    print(f"trinity-mini step: peak_memory_in_bytes {peak / 1e9:.2f} GB "
          f"(arguments {memory.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f})")
    params = sum(int(np.prod(s.shape))
                 for s in jax.tree.leaves(state.params))
    assert params == pytest.approx(705.47e6, rel=1e-4)
    assert 4.23e9 < peak < 15.7e9
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    flash = sorted(n.rsplit(".", 1)[0] if "." in n else n
                   for n in names if n.startswith("flash_"))
    # the dense layer's two windowed calls, straight, and each of the
    # four once in the loops' bodies: the kept output and logsumexp spare
    # the backward pass a second forward call
    assert flash == ["flash_bwd", "flash_bwd_win", "flash_bwd_win",
                     "flash_fwd", "flash_fwd_win", "flash_fwd_win"]
    assert all("gmm" in n or "sum_rows" in n for n in names
               if not n.startswith("flash_"))
    assert any("sum_rows" in n for n in names)
    entry = text[text.index("\nENTRY "):]
    bodies = re.findall(r" while\([^\n]*body=%([\w.\-]+)", entry)
    assert len(bodies) == 2
    # neither every expert for every token, nor a row for every (token,
    # choice) pair, nor whole float32 scores
    assert "[8192,16,1024]" not in text
    assert "[65536,2048]" not in text
    assert not re.search(r"f32\[(1,)?8192,8192\]", text)


@pytest.mark.parametrize("tiles", [(512, 512), (256, 512)])
def test_flash_kernels_compile_at_latent_attentions_heads(one_chip, tiles):
    """GLM-4.7-Flash's latent attention: 20 query heads on 20 key/value
    heads (group 1) of 256 against 8,192 causal keys: the two dense
    flash kernels pass Mosaic at twice the head size of every other
    cell, under the same VMEM limit."""
    from parallax_tpu.ops.pallas_attention import flash_attention

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, q_tile=tiles[0], block_k=tiles[1],
            interpret=False).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(1, 8192, 20, 256), sds(1, 8192, 20, 256),
                        sds(1, 8192, 20, 256))
    calls = _outside_fusions(compiled.as_text(),
                             'custom_call_target="tpu_custom_call"')
    assert [next(n for n in ("flash_fwd", "flash_bwd")
                 if n in c.split(" = ")[0]) for c in calls] \
        == ["flash_fwd", "flash_bwd"]
    assert "bf16[1,20,8192,256]" in calls[1]


def test_glm_step_compiles_at_published_widths_and_fits(topo):
    """GLM-4.7-Flash's training step as the benchmark's cell runs it (the
    dense layer, 4 expert layers and the MTP block, 8 of 64 experts,
    19,360 rows, one sequence of 8,192; every width as published)
    through ``Engine`` for the described v5e: 706.5 M parameters, a
    peak (``peak_memory_in_bytes``) between a quarter of the chip (4.23
    GB) and 15.6 GB of its 16.9; the flash kernels at heads of 256 ONCE a
    layer body in each direction (the dense layer's and the MTP block's
    straight, the loop's in its bodies: no forward kernel made again by
    the rematerialisation); ONE loop over the expert layers in each
    direction; the table looked up twice and updated once."""
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.core import engine as engine_lib, mesh as mesh_lib
    from parallax_tpu.models import glm4_moe_lite as glm

    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    cfg = glm.GlmConfig(vocab_size=19360, num_layers=5, experts_held=8,
                        warmup_steps=20000, num_partitions=1)
    model = glm.build_model(cfg, impls=("flash", "gmm"))
    mesh = mesh_lib.build_mesh(devices=[dev], num_partitions=1)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in glm.make_batch(
                 np.random.default_rng(0), 1, cfg.seq_len,
                 cfg.vocab_size).items()}
    engine = engine_lib.Engine(
        model, mesh, parallax.Config(run_option="HYBRID",
                                     sparse_grad_mode="slices"), batch)
    assert engine.plan.var_specs["emb"].is_sparse
    state = jax.eval_shape(engine._init_jit,
                           jax.ShapeDtypeStruct((), jnp.int32))
    assert state.model_state["router_bias"].shape == (5, 64)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    with mesh:
        compiled = engine._step_jit.trace(on_chip(state), on_chip(batch)) \
            .lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    peak = memory.peak_memory_in_bytes
    print(f"glm-4.7-flash step: peak_memory_in_bytes {peak / 1e9:.2f} GB "
          f"(arguments {memory.argument_size_in_bytes / 1e9:.2f}, "
          f"temporaries {memory.temp_size_in_bytes / 1e9:.2f})")
    params = sum(int(np.prod(s.shape))
                 for s in jax.tree.leaves(state.params))
    assert params == pytest.approx(706.5e6, rel=1e-3)
    assert 4.23e9 < peak < 15.6e9
    text = compiled.as_text()
    names = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    flash = sorted(n.rsplit(".", 1)[0] if "." in n else n
                   for n in names if n.startswith("flash_"))
    assert flash == ["flash_bwd"] * 3 + ["flash_fwd"] * 3
    assert all("gmm" in n or "sum_rows" in n or "adam_rows" in n
               for n in names if not n.startswith("flash_"))
    # the loops that carry the stream: the expert layers' scan, forward
    # and backward (the MTP block's experts bring small loops of their
    # own over the 8 held, straight in the step)
    entry = text[text.index("\nENTRY "):]
    loops = re.findall(r"[^\n]* while\([^\n]*", entry)
    assert sum("bf16[1,8192,2048]" in w.split(" while(")[0]
               for w in loops) == 2
    # neither every expert for every token, nor a row for every (token,
    # choice) pair, nor whole float32 scores
    assert "[8192,8,1536]" not in text
    assert "[32768,2048]" not in text
    assert not re.search(r"f32\[(1,)?8192,8192\]", text)
