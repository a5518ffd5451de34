"""Two-process elastic-recovery driver used by test_multihost.py (not a
test itself).

Attempt 0: worker 1 hard-kills itself mid-training (after the first
checkpoint). The launcher detects the death, tears the cluster down and
relaunches; the workers resume from the checkpoint and finish. The
result files record the attempt that completed and the step the resumed
session started from.

Exact-resume contract (ISSUE 9): batches are a pure function of the
step index, and every attempt appends its per-step losses (hex-exact)
to a shared log. The resumed attempt re-executes the steps attempt 0
already ran past the checkpoint (steps ckpt+1 .. crash) — those
overlap losses must be BIT-identical, proving the restore + replay is
exact, not just that the step counter looks right. The assertion runs
in-driver so the test stays skip-clean in env-blocked containers (the
multihost suite only runs where multi-process XLA:CPU works).
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import parallax_tpu as parallax  # noqa: E402
from parallax_tpu.common import consts  # noqa: E402
from parallax_tpu.models import simple  # noqa: E402

STEPS = 30
CRASH_STEP = 12
CKPT_EVERY = 5


def batch_for(step: int):
    """The batch that TRAINS step ``step`` (deterministic in the step
    index — the exact-resume replay contract: the resumed run feeds
    the same bits the interrupted run did)."""
    return simple.make_batch(np.random.default_rng(9000 + step), 32)


def _read_losses(path):
    out = {}
    try:
        with open(path) as f:
            for line in f:
                parts = line.split()
                if len(parts) == 3:
                    out.setdefault(int(parts[0]), {})[int(parts[1])] \
                        = parts[2]
    except OSError:
        pass
    return out


def main():
    out_path = sys.argv[1]
    ckpt_dir = sys.argv[2]
    attempt = int(os.environ.get(consts.PARALLAX_RESTART_ATTEMPT, "0"))
    model = simple.build_model(learning_rate=0.1)
    cfg = parallax.Config(run_option="AR", search_partitions=False)
    cfg.ckpt_config.ckpt_dir = ckpt_dir
    cfg.ckpt_config.save_ckpt_steps = CKPT_EVERY
    sess, num_workers, worker_id, _ = parallax.parallel_run(
        model, resource_info="localhost\n127.0.0.1",
        parallax_config=cfg)
    loss_log = f"{out_path}.losses.worker{worker_id}"
    first_step = sess.prepare(batch_for(1))
    step = first_step
    loss = None
    while step < STEPS:
        batch = batch_for(step + 1)
        loss, step = sess.run(["loss", "global_step"], feed_dict=batch)
        with open(loss_log, "a") as f:
            f.write(f"{attempt} {int(step)} {float(loss).hex()}\n")
        if attempt == 0 and step >= CRASH_STEP and worker_id == 1:
            os._exit(17)  # simulated hardware failure
    # Exact-resume check (resumed attempts only): the steps this
    # attempt re-ran that attempt 0 already logged must agree bit for
    # bit — same restored state, same step-keyed batches, same losses.
    overlap_checked = 0
    if attempt > 0:
        by_attempt = _read_losses(loss_log)
        prev = by_attempt.get(attempt - 1, {})
        cur = by_attempt.get(attempt, {})
        for s in sorted(set(prev) & set(cur)):
            assert prev[s] == cur[s], (
                f"resumed attempt {attempt} diverged from attempt "
                f"{attempt - 1} at step {s}: {cur[s]} != {prev[s]}")
            overlap_checked += 1
        assert overlap_checked > 0, (
            "resume produced no overlap steps to compare — the crash "
            "step / checkpoint cadence no longer overlap; fix the "
            "driver constants")
    with open(f"{out_path}.worker{worker_id}", "w") as f:
        f.write(f"attempt={attempt} first_step={first_step + 1} "
                f"step={step} loss={float(loss):.6f} "
                f"overlap_checked={overlap_checked}\n")
    sess.close()


if __name__ == "__main__":
    main()
