"""End-to-end multi-host control plane: the master spawns one process per
host (local-exec path of the ssh launcher), workers join the JAX
coordination service, train data-parallel across 2 processes x 4 devices,
and converge.

This is the multi-worker fixture the reference never had (SURVEY.md §4:
"multi-node without a cluster: not supported").
"""

import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_two_process_zigzag_ring_attention(tmp_path):
    """Zig-zag balanced causal ring attention across 2 processes: each
    host feeds its natural-order local slice, the in-graph permute makes
    the placement globally exact — trajectory must match a single-host
    run on the same global batches."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = str(tmp_path / "zz")
    env = dict(os.environ)
    env.update({
        "PARALLAX_COORDINATOR_PORT": str(port),
        "PYTHONPATH": os.getcwd() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PARALLAX_RUN_OPTION", None)
    proc = subprocess.run(
        [sys.executable, "tests/multihost_zigzag_driver.py", out],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]

    losses = {}
    for wid in (0, 1):
        path = f"{out}.worker{wid}"
        assert os.path.exists(path), proc.stderr[-2000:]
        losses[wid] = [float(x) for x in open(path).read().split()]
    assert losses[0] == losses[1], "workers disagree on the loss"

    # single-host reference on the same global batches
    import numpy as np
    import parallax_tpu as parallax
    from tests import multihost_zigzag_driver as drv
    from parallax_tpu.models import long_context as lc
    cfg = lc.tiny_config(max_len=drv.T)
    cfg.zigzag = True
    sess, *_ = parallax.parallel_run(
        lc.build_model(cfg),
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False),
        num_partitions=8)
    ref = []
    for step in range(drv.STEPS):
        batch = lc.make_batch(np.random.default_rng(step), drv.B, drv.T,
                              cfg.vocab_size)
        ref.append(float(sess.run("loss", feed_dict=batch)))
    sess.close()
    np.testing.assert_allclose(losses[0], ref, rtol=1e-4)


@pytest.mark.slow
def test_elastic_restart_resumes_from_checkpoint(tmp_path):
    """Worker 1 hard-dies mid-training on attempt 0; with
    PARALLAX_MAX_RESTARTS=1 the launcher relaunches the cluster and the
    workers resume from the last checkpoint instead of step 0."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = str(tmp_path / "elastic")
    ckpt = str(tmp_path / "ckpt")
    env = dict(os.environ)
    env.update({
        "PARALLAX_COORDINATOR_PORT": str(port),
        "PARALLAX_MAX_RESTARTS": "1",
        "PYTHONPATH": os.getcwd() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PARALLAX_RUN_OPTION", None)
    proc = subprocess.run(
        [sys.executable, "tests/multihost_elastic_driver.py", out, ckpt],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]

    from tests import multihost_elastic_driver as drv
    for wid in (0, 1):
        path = f"{out}.worker{wid}"
        assert os.path.exists(path), proc.stderr[-2000:]
        fields = dict(kv.split("=")
                      for kv in open(path).read().split())
        # the run that wrote results is the relaunch...
        assert fields["attempt"] == "1", fields
        # ...and it resumed from the checkpoint, not step 0
        assert int(fields["first_step"]) > drv.CKPT_EVERY, fields
        assert fields["step"] == str(drv.STEPS), fields


@pytest.mark.slow
def test_straggler_host_named_in_aggregated_artifact(tmp_path):
    """Forensics acceptance (ISSUE 5): 2 processes, worker 1 with an
    injected per-step host delay — the cross-process aggregation over
    the coordinator channel must NAME the delayed host in the report
    every process receives AND in the flight-dump artifact."""
    import json
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = str(tmp_path / "straggler")
    flight_dir = str(tmp_path / "flight")
    os.makedirs(flight_dir, exist_ok=True)
    env = dict(os.environ)
    env.update({
        "PARALLAX_COORDINATOR_PORT": str(port),
        "PYTHONPATH": os.getcwd() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PARALLAX_RUN_OPTION", None)
    proc = subprocess.run(
        [sys.executable, "tests/multihost_straggler_driver.py", out,
         flight_dir],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]

    results = {}
    for wid in (0, 1):
        path = f"{out}.worker{wid}"
        assert os.path.exists(path), proc.stderr[-2000:]
        results[wid] = json.load(open(path))
    # every process received the same verdict: process 1 is the
    # straggler, by name
    for wid, doc in results.items():
        rep = doc["report"]
        assert rep["num_hosts"] == 2, rep
        assert rep["stragglers"] == [1], rep
        assert rep["hosts"][1]["straggler"] is True
        assert rep["hosts"][1]["mean_ms"] > rep["hosts"][0]["mean_ms"]
    # and the flight artifact carries the named straggler in-file
    for wid, doc in results.items():
        flight = json.load(open(doc["flight_path"]))
        assert flight["host_report"]["stragglers"] == [1], \
            flight["host_report"]
        assert flight["process_index"] == wid


@pytest.mark.slow
def test_two_process_launch_and_training(tmp_path):
    import socket
    with socket.socket() as s:  # grab a free port; avoids collisions
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = str(tmp_path / "result")
    env = dict(os.environ)
    env.update({
        "PARALLAX_COORDINATOR_PORT": str(port),
        "PYTHONPATH": os.getcwd() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PARALLAX_RUN_OPTION", None)
    proc = subprocess.run(
        [sys.executable, "tests/multihost_driver.py", out],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]

    results = {}
    for wid in (0, 1):
        path = f"{out}.worker{wid}"
        assert os.path.exists(path), (
            f"worker {wid} left no result; master stderr:\n"
            + proc.stderr[-2000:])
        results[wid] = open(path).read().strip()

    for wid, line in results.items():
        fields = dict(kv.split("=") for kv in line.split())
        assert fields["workers"] == "2", line
        assert fields["replicas"] == "4", line
        assert fields["step"] == "30", line
        # converged toward y = 10x - 5 on the combined global batch
        assert abs(float(fields["w"]) - 10.0) < 1.5, line
        assert abs(float(fields["b"]) + 5.0) < 1.5, line
    # replicated state identical across workers
    w0 = dict(kv.split("=") for kv in results[0].split())
    w1 = dict(kv.split("=") for kv in results[1].split())
    assert w0["w"] == w1["w"] and w0["b"] == w1["b"], (results[0],
                                                      results[1])


@pytest.mark.slow
def test_two_process_sparse_cross_replica_combine(tmp_path):
    """Multi-slice sparse combine across a process boundary (VERDICT r3
    item 4): the 2-process x 4-device mesh must nest each shard ring
    inside one process (asserted in the driver), auto-pick the SPARSE
    cross-replica table-grad combine (asserted in the driver), and its
    trajectory must match a single-host run FORCED to the dense psum
    combine on the same global batches."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = str(tmp_path / "sparse")
    env = dict(os.environ)
    env.update({
        "PARALLAX_COORDINATOR_PORT": str(port),
        "PYTHONPATH": os.getcwd() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PARALLAX_RUN_OPTION", None)
    proc = subprocess.run(
        [sys.executable, "tests/multihost_sparse_driver.py", out],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]

    losses = {}
    for wid in (0, 1):
        path = f"{out}.worker{wid}"
        assert os.path.exists(path), proc.stderr[-2000:]
        losses[wid] = [float(x) for x in open(path).read().split()]
    assert losses[0] == losses[1], "workers disagree on the loss"

    # single-host reference on the same global batches, dense combine
    import numpy as np
    import parallax_tpu as parallax
    from tests import multihost_sparse_driver as drv
    from parallax_tpu.models import lm1b
    cfg = lm1b.tiny_config(num_partitions=drv.NUM_PARTITIONS)
    comm = parallax.CommunicationConfig(
        ps_config=parallax.PSConfig(cross_replica_sparse=False))
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False,
                                        communication_config=comm),
        num_partitions=drv.NUM_PARTITIONS)
    sess.run([], feed_dict=lm1b.make_batch(
        np.random.default_rng(0), drv.B, drv.T, cfg.vocab_size))
    # the forced hint took: the dense combine is in the trace
    recs = sess.engine.sparse_wire_bytes_per_step()["per_lookup"]
    assert recs and not any(r["cross_replica_sparse"] for r in recs), recs
    ref = []
    for step in range(1, drv.STEPS):
        batch = lm1b.make_batch(np.random.default_rng(step), drv.B,
                                drv.T, cfg.vocab_size)
        ref.append(float(sess.run("loss", feed_dict=batch)))
    sess.close()
    np.testing.assert_allclose(losses[0], ref, rtol=1e-4)


@pytest.mark.slow
def test_four_process_sparse_combine_elastic_restart(tmp_path):
    """VERDICT r4 next item 5: the N-machine case — repl=4 crossing
    THREE process boundaries (4 processes x 2 devices), hybrid sparse
    cross-replica combine AND an elastic kill/restart on the same
    topology. Worker 3 dies on attempt 0 after the first checkpoint;
    the relaunch resumes and the completed, per-step-seeded trajectory
    must match an uninterrupted single-process run on the same mesh
    shape."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = str(tmp_path / "fourproc")
    ckpt = str(tmp_path / "ckpt4")
    env = dict(os.environ)
    env.update({
        "PARALLAX_COORDINATOR_PORT": str(port),
        "PARALLAX_MAX_RESTARTS": "1",
        "PYTHONPATH": os.getcwd() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PARALLAX_RUN_OPTION", None)
    proc = subprocess.run(
        [sys.executable, "tests/multihost_4proc_driver.py", out, ckpt],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]

    from tests import multihost_4proc_driver as drv
    results = {}
    for wid in range(drv.NUM_WORKERS):
        path = f"{out}.worker{wid}"
        assert os.path.exists(path), (
            f"worker {wid} left no result; master stderr:\n"
            + proc.stderr[-3000:])
        lines = open(path).read().splitlines()
        meta = dict(kv.split("=") for kv in lines[0].split())
        # the completed run is the relaunch, resumed from the ckpt
        assert meta["attempt"] == "1", meta
        assert int(meta["first_step"]) == drv.CKPT_EVERY + 1, meta
        results[wid] = [(int(s), float(l))
                        for s, l in (ln.split() for ln in lines[1:])]
    # all four processes agree on the trajectory
    assert all(results[w] == results[0]
               for w in range(1, drv.NUM_WORKERS)), results
    assert results[0][-1][0] == drv.STEPS, results[0]

    # uninterrupted single-process reference on the SAME mesh shape
    # (conftest gives this process 8 virtual devices -> [repl=4, shard=2])
    import numpy as np
    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b
    cfg = lm1b.tiny_config(num_partitions=drv.NUM_PARTITIONS)
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(run_option="HYBRID",
                                        search_partitions=False),
        num_partitions=drv.NUM_PARTITIONS)
    ref = {}
    for step in range(1, drv.STEPS + 1):
        ref[step] = float(sess.run("loss",
                                   feed_dict=drv.global_batch(step)))
    sess.close()
    got = dict(results[0])
    for step, loss in got.items():
        np.testing.assert_allclose(loss, ref[step], rtol=1e-4,
                                   err_msg=f"step {step}")
