"""Four-process sparse-combine + elastic-restart driver
(test_multihost.py; not a test itself).

VERDICT r4 next item 5: scale the multi-process evidence past 2x4 — the
N-machine case of the reference's two-level global sync (reference:
core/python/common/graph_transform_lib.py:1558-1946 aggregates sparse
updates locally per machine, then globally across machines), exercised
here as repl=4 crossing THREE process boundaries on a 4-process x
2-device mesh, with BOTH the hybrid sparse cross-replica combine and an
elastic kill/restart on the same topology.

Attempt 0: worker 3 hard-dies after the post-checkpoint step. The
launcher relaunches; workers restore the checkpoint and finish. Batches
are seeded by global step, so the completed trajectory must equal an
uninterrupted single-process run on the same mesh shape — the test
asserts that parity.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import parallax_tpu as parallax  # noqa: E402
from parallax_tpu.common import consts  # noqa: E402
from parallax_tpu.models import lm1b  # noqa: E402

STEPS, B, T = 8, 16, 8
NUM_PARTITIONS = 2  # = devices per process -> shard rings nest per process
NUM_WORKERS = 4
CKPT_EVERY = 3
CRASH_STEP = 4      # > first checkpoint (step 3)
RESOURCES = "localhost\n127.0.0.1\n127.0.0.2\n127.0.0.3"


def global_batch(step: int):
    """Deterministic per-step global batch — identical in every process
    and in the single-process reference run."""
    return lm1b.make_batch(np.random.default_rng(step), B, T,
                           lm1b.tiny_config().vocab_size)


def main():
    out_path, ckpt_dir = sys.argv[1], sys.argv[2]
    attempt = int(os.environ.get(consts.PARALLAX_RESTART_ATTEMPT, "0"))
    cfg = lm1b.tiny_config(num_partitions=NUM_PARTITIONS)
    pcfg = parallax.Config(run_option="HYBRID", search_partitions=False)
    pcfg.ckpt_config.ckpt_dir = ckpt_dir
    pcfg.ckpt_config.save_ckpt_steps = CKPT_EVERY
    sess, num_workers, worker_id, _ = parallax.parallel_run(
        lm1b.build_model(cfg), resource_info=RESOURCES,
        parallax_config=pcfg, num_partitions=NUM_PARTITIONS)
    assert num_workers == NUM_WORKERS

    def local(batch):
        q = B // NUM_WORKERS
        return {k: v[worker_id * q:(worker_id + 1) * q]
                for k, v in batch.items()}

    # build the engine (and restore any checkpoint) WITHOUT running a
    # step, so the first real step's batch can be seeded by its true
    # global step even on the resumed attempt
    start = sess.prepare(local(global_batch(1)))

    # (a) mesh topology: [repl=4, shard=2]; every shard ring lives
    # inside ONE process; 'repl' crosses three process boundaries
    rows = sess.engine.mesh.devices
    assert rows.shape == (NUM_WORKERS, NUM_PARTITIONS), rows.shape
    row_procs = [{d.process_index for d in row} for row in rows]
    assert all(len(procs) == 1 for procs in row_procs), row_procs
    assert len(set().union(*row_procs)) == NUM_WORKERS, row_procs

    # (b) + (c): train on per-step-seeded global batches; after the
    # first traced step, assert the static chooser picked the SPARSE
    # cross-replica combine for the emb table on this 4-replica
    # workload (auto, no hint); crash worker 3 on attempt 0 after the
    # post-checkpoint step completes
    losses = []
    first_step = start + 1
    for step in range(start + 1, STEPS + 1):
        loss = float(sess.run("loss", feed_dict=local(global_batch(step))))
        losses.append((step, loss))
        if step == first_step:
            recs = sess.engine.sparse_wire_bytes_per_step()["per_lookup"]
            emb_shape = (cfg.padded_vocab, cfg.emb_dim)
            emb_recs = [r for r in recs
                        if tuple(r["table_shape"]) == emb_shape]
            assert emb_recs, recs
            for r in emb_recs:
                assert r["cross_replica_sparse"], r
        if attempt == 0 and step >= CRASH_STEP and worker_id == 3:
            os._exit(17)  # simulated hardware failure

    with open(f"{out_path}.worker{worker_id}", "w") as f:
        f.write(f"attempt={attempt} first_step={first_step}\n")
        for step, loss in losses:
            f.write(f"{step} {loss:.6f}\n")
    sess.close()


if __name__ == "__main__":
    main()
