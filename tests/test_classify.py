"""Tests for trace-time dense/sparse classification (core/classify.py).

Parity target: the reference's IndexedSlices-vs-Tensor gradient
classification (common/runner.py:40-60) — a variable is sparse iff it is
consumed only through gather/embedding-lookup.
"""

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.core.classify import classify_params, leaf_path_names


def _batch():
    return {"ids": jnp.zeros((4,), jnp.int32),
            "x": jnp.zeros((4, 8), jnp.float32)}


def test_pure_embedding_is_sparse():
    params = {"emb": jnp.zeros((16, 8)), "w": jnp.zeros((8, 2))}

    def loss(params, batch):
        rows = jnp.take(params["emb"], batch["ids"], axis=0)
        return jnp.sum(rows @ params["w"])

    specs = classify_params(loss, params, _batch())
    assert specs["emb"].is_sparse
    assert specs["emb"].reason == "all uses are gather operands"
    assert not specs["w"].is_sparse


def test_gathered_and_dense_use_is_dense():
    # A tied embedding also used as a softmax matrix gets a dense gradient
    # in the reference too (grad = Tensor, not IndexedSlices).
    params = {"emb": jnp.zeros((16, 8))}

    def loss(params, batch):
        rows = jnp.take(params["emb"], batch["ids"], axis=0)
        logits = rows @ params["emb"].T
        return jnp.sum(logits)

    specs = classify_params(loss, params, _batch())
    assert not specs["emb"].is_sparse
    assert specs["emb"].reason == "gathered but also used densely"


def test_a_tied_table_in_chunks_and_casts_is_still_dense():
    """The mixed-use branch through what a real model puts around it: the
    lookup behind the embedding op, the head behind a cast and inside a
    rematerialised scan body. One dense use is enough; no name decides."""
    from parallax_tpu.ops import embedding as emb_ops
    params = {"emb": jnp.zeros((16, 8)), "w": jnp.zeros((3, 8, 8))}

    def loss(params, batch):
        h = emb_ops.embedding_lookup(params["emb"], batch["ids"])

        @jax.checkpoint
        def body(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, h, params["w"])
        head = params["emb"].astype(jnp.bfloat16).T
        return jnp.sum(h.astype(jnp.bfloat16) @ head)

    specs = classify_params(loss, params, _batch())
    assert not specs["emb"].is_sparse
    assert specs["emb"].reason == "gathered but also used densely"
    assert specs["w"].reason == "no gather use"
    # untied, the same table is sparse: the head's use was what decided
    untied = {**params, "head": jnp.zeros((16, 8))}

    def loss_untied(params, batch):
        h = emb_ops.embedding_lookup(params["emb"], batch["ids"])
        return jnp.sum(h @ params["head"].T)

    specs = classify_params(loss_untied, untied, _batch())
    assert specs["emb"].is_sparse and not specs["head"].is_sparse


def test_gather_through_cast_is_sparse():
    params = {"emb": jnp.zeros((16, 8), jnp.bfloat16)}

    def loss(params, batch):
        table = params["emb"].astype(jnp.float32)
        return jnp.sum(jnp.take(table, batch["ids"], axis=0))

    specs = classify_params(loss, params, _batch())
    assert specs["emb"].is_sparse


def test_gather_inside_jitted_subfunction():
    params = {"emb": jnp.zeros((16, 8)), "w": jnp.zeros((8, 2))}

    @jax.jit
    def lookup(table, ids):
        return jnp.take(table, ids, axis=0)

    def loss(params, batch):
        return jnp.sum(lookup(params["emb"], batch["ids"])
                       @ params["w"])

    specs = classify_params(loss, params, _batch())
    assert specs["emb"].is_sparse
    assert not specs["w"].is_sparse


def test_gather_inside_scan():
    params = {"emb": jnp.zeros((16, 8))}

    def loss(params, batch):
        def body(carry, i):
            return carry + jnp.sum(
                jnp.take(params["emb"], batch["ids"] + i, axis=0)), None
        total, _ = jax.lax.scan(body, 0.0, jnp.arange(3))
        return total

    specs = classify_params(loss, params, _batch())
    assert specs["emb"].is_sparse


def test_user_override_wins():
    params = {"emb": jnp.zeros((16, 8))}

    def loss(params, batch):
        return jnp.sum(jnp.take(params["emb"], batch["ids"], axis=0))

    specs = classify_params(loss, params, _batch(),
                            dense_override=("emb",))
    assert not specs["emb"].is_sparse
    assert specs["emb"].reason == "user override"


def test_dense_only_model():
    params = {"w": jnp.zeros((8, 2)), "b": jnp.zeros((2,))}

    def loss(params, batch):
        return jnp.sum(batch["x"] @ params["w"] + params["b"])

    specs = classify_params(loss, params, _batch())
    assert all(not s.is_sparse for s in specs.values())


def test_leaf_path_names_nested():
    tree = {"layer": {"w": np.zeros(2), "b": np.zeros(2)},
            "emb": np.zeros(2)}
    names = leaf_path_names(tree)
    assert set(names) == {"layer/w", "layer/b", "emb"}
