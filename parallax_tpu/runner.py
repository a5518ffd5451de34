"""`parallel_run` — the single entry point.

Reference: common/runner.py:139-193 — the user hands over an unmodified
single-GPU graph plus a resource file; the master classifies gradients,
picks the backend, launches the cluster, and each worker gets back
``(sess, num_workers, worker_id, num_replicas_per_worker)``.

Same contract here, with a Model instead of a graph:

    sess, num_workers, worker_id, num_replicas = parallax.parallel_run(
        model, resource_info, sync=True, parallax_config=config)
    for _ in range(steps):
        loss, step = sess.run(["loss", "global_step"],
                              feed_dict={"x": xs, "y": ys})

Differences forced by SPMD (SURVEY.md §7 hard-part 6): worker_id /
num_workers are (host process index, host process count) and
num_replicas_per_worker is the local device count — the same values the
reference computes from its resource file, minus the ssh bootstrap when the
TPU runtime already started one process per host.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Optional, Tuple

import jax

from parallax_tpu.common import consts
from parallax_tpu.common.config import ParallaxConfig
from parallax_tpu.common.lib import (HostInfo, deserialize_resource_info,
                                     parallax_log, parse_resource_info)
from parallax_tpu import launcher, shard as shard_lib
from parallax_tpu.core.engine import Model
from parallax_tpu.obs import trace
from parallax_tpu.parallel.partitions import PartitionSearch, get_partitioner
from parallax_tpu.session import ParallaxSession


def parallel_run(model: Model,
                 resource_info: Optional[str] = None,
                 sync: bool = True,
                 parallax_config: Optional[ParallaxConfig] = None,
                 seed: int = 0,
                 num_partitions: Optional[int] = None
                 ) -> Tuple[ParallaxSession, int, int, int]:
    """``num_partitions`` pins the shard-axis size (the reference's
    embedding partition count); env PARALLAX_PARTITIONS overrides it, and
    leaving both unset enables the auto-search when
    PARALLAX_MIN_PARTITIONS is set. A ``Config.tune_config`` supersedes
    the 1-D search entirely: the session plans through
    ``tune.MeshSearch`` over (dp x tp) mesh shapes and run options,
    with ``num_partitions`` (when given) only seeding the base plan.

    Its wall seconds are the first part of the session's
    ``startup.api_s`` (``session._startup_entry``)."""
    t0 = time.perf_counter()
    with trace.span("parallax.parallel_run"):
        out = _parallel_run(model, resource_info, sync, parallax_config,
                            seed, num_partitions)
    out[0].metrics.counter("startup.api_s").inc(time.perf_counter() - t0)
    return out


def _parallel_run(model, resource_info, sync, parallax_config, seed,
                  num_partitions):
    config = parallax_config or ParallaxConfig()
    config.set_sync(sync)

    role = os.environ.get(consts.PARALLAX_RUN_OPTION)
    if role == "WORKER":
        hosts = deserialize_resource_info(
            os.environ[consts.PARALLAX_RESOURCE_INFO])
        config.set_resource_info(hosts)
        launcher.init_worker_distributed()
    else:
        hosts = (parse_resource_info(resource_info)
                 if resource_info is not None else [HostInfo("localhost")])
        config.set_resource_info(hosts)
        if len(hosts) > 1:
            # Master path: spawn one process per host and exit, exactly like
            # the reference master (runner.py:187 sys.exit()).
            rc = launcher.launch_workers(
                hosts, config.redirect_path,
                has_checkpoint=config.ckpt_config.ckpt_dir is not None)
            sys.exit(rc)

    unused = config.unused_knobs()
    if unused:
        parallax_log.info(
            "config knobs with no TPU effect (accepted for parity): %s",
            unused)

    num_workers = jax.process_count()
    worker_id = jax.process_index()
    num_replicas_per_worker = max(1, jax.local_device_count())
    shard_lib._install(num_workers, worker_id)

    search = None
    min_p = os.environ.get(consts.PARALLAX_MIN_PARTITIONS)
    tune_on = (config.tune_config is not None
               and config.tune_config.enabled)
    if os.environ.get(consts.PARALLAX_PARTITIONS):
        num_partitions = get_partitioner()
    elif num_partitions is not None:
        pass  # explicit argument wins over the 1-D auto-search
    elif tune_on:
        # the mesh auto-tuner (tune/, ISSUE 10) supersedes the 1-D
        # partition search: the session plans through MeshSearch, with
        # num_partitions (when given) only seeding the base plan
        parallax_log.info(
            "mesh auto-tuner enabled (tune_config): searching "
            "(dp x tp) x run_option, top_k=%d",
            config.tune_config.top_k)
    elif config.search_partitions and min_p:
        search = PartitionSearch(int(min_p), jax.device_count())
        num_partitions = search.first_candidate()
        parallax_log.info("partition auto-search enabled, starting at p=%d",
                          num_partitions)

    sess = ParallaxSession(model, config, num_workers, worker_id,
                           num_replicas_per_worker,
                           num_partitions=num_partitions,
                           partition_search=search, seed=seed)
    parallax_log.info(
        "parallel_run ready: %d worker(s), %d local replica(s), "
        "run_option=%s", num_workers, num_replicas_per_worker,
        config.run_option)
    return sess, num_workers, worker_id, num_replicas_per_worker
