"""Two-process sparse cross-replica-combine driver (test_multihost.py).

The multi-slice re-design of the reference's hybrid centerpiece
(reference: core/python/common/graph_transform_lib.py:1372-1556 ships
aggregated (ids, values) over the slow network between PS shards): on
the 2-process × 4-device mesh the shard rings must nest INSIDE each
process (core/mesh._order_by_domain) so the 'repl' axis alone crosses
the process boundary, and the table-grad combine across 'repl' must be
the SPARSE gather of deduped (ids, row-grads) — picked statically by
bytes — with a trajectory identical to the dense [rows/shard, dim] psum.

Each worker asserts the ring nesting and the static sparse pick, then
trains the tiny LM1B hybrid model on seeded global batches and writes
its loss trajectory; the test compares against a single-host run forced
to the DENSE combine on the same global batches.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import parallax_tpu as parallax  # noqa: E402
from parallax_tpu.models import lm1b  # noqa: E402
from parallax_tpu.ops import embedding as emb_ops  # noqa: E402

STEPS, B, T = 6, 16, 8
NUM_PARTITIONS = 4  # = devices per process -> rings nest per process


def main():
    out_path = sys.argv[1]
    cfg = lm1b.tiny_config(num_partitions=NUM_PARTITIONS)
    model = lm1b.build_model(cfg)
    sess, num_workers, worker_id, _ = parallax.parallel_run(
        model, resource_info="localhost\n127.0.0.1",
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False),
        num_partitions=NUM_PARTITIONS)
    assert num_workers == 2

    # first step builds the engine (lazy); each worker feeds its half
    rng0 = np.random.default_rng(0)
    batch0 = lm1b.make_batch(rng0, B, T, cfg.vocab_size)
    half = B // num_workers
    sess.run([], feed_dict={
        k: v[worker_id * half:(worker_id + 1) * half]
        for k, v in batch0.items()})

    # (a) ring nesting: every 'shard' row of the mesh lives inside ONE
    # process; 'repl' is what crosses the boundary
    mesh = sess.engine.mesh
    rows = mesh.devices  # [repl, shard] object array
    assert rows.shape == (2, NUM_PARTITIONS), rows.shape
    row_procs = [{d.process_index for d in row} for row in rows]
    assert all(len(procs) == 1 for procs in row_procs), row_procs
    assert row_procs[0] != row_procs[1], row_procs

    # (b) the static chooser picks the sparse cross-replica combine for
    # the emb table on this workload (auto mode, no hint forced)
    recs = sess.engine.sparse_wire_bytes_per_step()["per_lookup"]
    emb_shape = (cfg.padded_vocab, cfg.emb_dim)
    emb_recs = [r for r in recs if tuple(r["table_shape"]) == emb_shape]
    assert emb_recs, recs
    for r in emb_recs:
        assert r["cross_replica_sparse"], r

    # (c) trajectory on seeded global batches; each worker feeds its
    # process-local half of the global batch (batch dim is device-major
    # over the mesh, so worker w owns rows [w*B/2, (w+1)*B/2))
    losses = []
    for step in range(1, STEPS):
        g = lm1b.make_batch(np.random.default_rng(step), B, T,
                            cfg.vocab_size)
        local = {k: v[worker_id * half:(worker_id + 1) * half]
                 for k, v in g.items()}
        losses.append(float(sess.run("loss", feed_dict=local)))
    with open(f"{out_path}.worker{worker_id}", "w") as f:
        f.write(" ".join(f"{x:.6f}" for x in losses) + "\n")
    sess.close()


if __name__ == "__main__":
    main()
