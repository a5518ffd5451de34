"""The LM1B trainer, built as a user builds it: ``parallax.parallel_run``
on ``models/lm1b.build_model``, nothing reached around.

The configuration file's ``model`` block holds ``LM1BConfig``'s fields
under their own names; ``deployment`` holds the plan
(``run_option``, ``sparse_grad_mode``, ``search_partitions``). The
weights are made on the device from ``--seed`` by the engine's own
jitted initialiser.
"""

from __future__ import annotations

import dataclasses

# The comparison with the reference runs on EVAL_BATCHES batches of
# EVAL_SEQUENCES_PER_CHIP sequences a chip: one batch is what the
# 793,470-wide float32 logits of both sides leave room for beside the
# training state, and over four of them the errors of bfloat16 and of
# the 8-bit control stand clear of each other (PERF.md, PR 23).
EVAL_BATCHES = 4
EVAL_SEQUENCES_PER_CHIP = 8
# The negative control: the same comparison with the system fed the
# LSTM's two weight matrices rounded to 8 bits (``to_8bit`` below) must
# FAIL, or the tolerances could not see matrix products fed a narrower
# type than the configuration states. ``w`` feeds the gate products of
# the recurrence, ``w_proj`` the projection whose output the softmax's
# products read.
CONTROL_ROUNDS = ("w", "w_proj")


def tolerances(cell) -> dict:
    """The configuration file's ``tolerances`` (set from what the chip
    showed at the published widths: PERF.md, PR 23), with its
    ``rehearse_tolerances`` at the rehearsal's sizes, where the same
    arithmetic leaves other errors."""
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    return {"nll_rms_tol": float(tol["nll_rms_tol"]),
            "grad_fro_tol": {k: float(v)
                             for k, v in tol["grad_fro_tol"].items()}}


def model_config(cell):
    import jax.numpy as jnp
    from parallax_tpu.models import lm1b

    m = dict(cell.model)
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    m["table_dtype"] = jnp.dtype(m["table_dtype"])
    return lm1b.LM1BConfig(num_partitions=cell.chips, **m)


class System:
    def __init__(self, cell, session, cfg, reference):
        self.cell = cell
        self.session = session
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self._reference = reference

    def static_checks(self) -> list:
        """The smoke's checks: the three tables row-sharded
        ``padded_vocab / chips`` on every chip, and the recurrence run
        by the kernels forward and backward."""
        from parallax_tpu.ops import pallas_lstm

        n = self.cell.chips
        failures = []
        rows = self.cfg.padded_vocab // n
        params = self.session.state.params
        for name in ("emb", "softmax_w", "softmax_b"):
            arr = params[name]
            shard_rows = arr.sharding.shard_shape(arr.shape)[0]
            on = {s.device for s in arr.addressable_shards}
            if shard_rows != rows or len(on) != n:
                failures.append(
                    f"{name}: {shard_rows} rows a shard on {len(on)} "
                    f"device(s), want {rows} on {n}")
        if self.cfg.lstm_impl == "pallas":
            recs = pallas_lstm.trace_records(self.session.engine.mesh)
            bwd = sorted({r["bwd"] for r in recs})
            want = ["scan"] if self.cell.rehearse else ["kernel"]
            if bwd != want:
                failures.append(
                    f"lstm backward executors {bwd}, want {want}")
        return failures

    def exact_eval(self):
        """``evaluate(lstm, batch) -> (nll, grads)``: the system's own
        exact-softmax evaluation of the session's parameters where the
        plan placed them, with the LSTM's arrays as given. It is the
        model's ``call_loss`` with ``full_softmax=True`` and dropout
        off (the path ``examples/lm1b_eval.py`` uses), traced under the
        engine's mesh scope so that the lookups and the recurrence take
        the sharded path and the kernels the training step takes, and
        differentiated by ``jax.grad`` through the kernels' backward.

        ``call_loss`` returns ``sum(l * w) / sum(w)`` over the
        positions' losses ``l``, whose derivative in ``w_i`` is ``(l_i
        - loss) / sum(w)``: so ``l`` comes out of the same call, with
        nothing of the model reached around."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import lm1b
        from parallax_tpu.ops import embedding as emb_ops

        engine = self.session.engine
        params = self.session.state.params
        eval_model = lm1b.build_model(
            dataclasses.replace(self.cfg, keep_prob=1.0),
            full_softmax=True)

        def loss_of(lstm, w, params, batch):
            with emb_ops.sharded_lookup_scope(
                    engine.mesh, engine.plan.sharded_shapes):
                loss, _metrics, _state = eval_model.call_loss(
                    {**params, "lstm": lstm}, {**batch, "w": w},
                    jax.random.PRNGKey(0))
            return loss

        @jax.jit
        def run(lstm, params, batch):
            loss, (g_lstm, g_w) = jax.value_and_grad(
                loss_of, argnums=(0, 1))(lstm, batch["w"], params, batch)
            return loss + g_w * jnp.sum(batch["w"]), g_lstm

        def evaluate(lstm, batch):
            with engine.mesh:
                nll, grads = run(lstm, params, batch)
            return np.asarray(nll), {k: np.asarray(v)
                                     for k, v in grads.items()}

        return evaluate

    def reference_check(self, seed: int) -> dict:
        import jax
        import numpy as np

        generator = self.cell.plugin("generators",
                                     self.cell.traffic["generator"])
        rows = generator.make_eval(
            self.cell.mix, seed, self.vocab_size,
            EVAL_BATCHES * EVAL_SEQUENCES_PER_CHIP * self.cell.chips)
        batches = [{k: np.split(v, EVAL_BATCHES)[i] for k, v in rows.items()}
                   for i in range(EVAL_BATCHES)]
        params = self.session.state.params
        lstm = params["lstm"]
        want = [self._reference.nll_and_lstm_grads(params, b,
                                                   self.cell.model)
                for b in batches]
        evaluate = self.exact_eval()
        tol = tolerances(self.cell)
        out = compare([evaluate(lstm, b) for b in batches], want, tol)
        out["sequences"] = int(rows["x"].shape[0])

        def to_8bit(x):
            # 1 sign, 3 mantissa bits and the exponent's full range: an
            # 8-bit float under a perfect scale. (A pair of casts would
            # do on the CPU; the TPU compiler removes it as excess
            # precision, and ``reduce_precision`` is the operation it
            # must keep.)
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=3)

        rounded = {k: to_8bit(v) if k in CONTROL_ROUNDS else v
                   for k, v in lstm.items()}
        control = compare([evaluate(rounded, b) for b in batches], want, tol)
        out["control_lstm_weights_8bit"] = {
            "nll_rms_err": control["nll_rms_err"],
            "grad_fro_err": control["grad_fro_err"],
            "caught": not control["ok"]}
        # At the rehearsal's sizes the control proves nothing about the
        # chip's tolerances, and how far its tiny model has trained in a
        # few seconds of a CPU differs run by run: it is reported there,
        # and decides only a chip run.
        if not self.cell.rehearse:
            out["ok"] = out["ok"] and not control["ok"]
        return out


def compare(got, want, tol: dict) -> dict:
    """The system's ``(nll, grads)`` of each batch against the
    reference's. Judged: the root mean square, over every position of
    every batch, of the difference of their negative log-likelihoods,
    in nats (a difference at one position counts by its square, and
    differences do not cancel as they do in the mean NLL); and for each
    gradient array the Frobenius norm of the difference over the norm
    of the reference's, root mean square over the batches, against the
    array's own tolerance (the projection's gradient repeats far more
    closely than the two that run back through the recurrence, and
    carries the negative control). Reported
    beside them: the largest difference at any one position, and the
    largest in each gradient array over the array's largest entry. The
    largest of some hundred draws is the noisiest reading of the same
    distribution, and a bound on it has to sit where the negative
    control passes too."""
    import numpy as np

    d = np.concatenate([g[0] - w[0] for g, w in zip(got, want)])
    nll_rms = float(np.sqrt(np.mean(d * d)))
    grad_fro, grad_max = {}, {}
    for k in sorted(want[0][1]):
        diffs = [(g[1][k].astype(np.float32) - w[1][k], w[1][k])
                 for g, w in zip(got, want)]
        grad_fro[k] = float(np.sqrt(np.mean(
            [(np.linalg.norm(dg) / np.linalg.norm(ref)) ** 2
             for dg, ref in diffs])))
        grad_max[k] = float(max(np.max(np.abs(dg)) / np.max(np.abs(ref))
                                for dg, ref in diffs))
    return {"system_nll": float(np.mean([g[0] for g in got])),
            "reference_nll": float(np.mean([w[0] for w in want])),
            "nll_rms_err": nll_rms, "nll_max_err": float(np.max(np.abs(d))),
            "grad_fro_err": grad_fro, "grad_max_err": grad_max, **tol,
            "ok": bool(nll_rms <= tol["nll_rms_tol"]
                       and all(grad_fro[k] <= tol["grad_fro_tol"][k]
                               for k in grad_fro))}


def build(cell, seed: int) -> System:
    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b
    from parallax_tpu.ops import pallas_lstm

    cfg = model_config(cell)
    dep = cell.deployment
    pallas_lstm.reset_trace_records()
    sess, *_ = parallax.parallel_run(
        lm1b.build_model(cfg),
        parallax_config=parallax.Config(
            run_option=dep["run_option"],
            sparse_grad_mode=cfg.sparse_grad_mode,
            search_partitions=bool(dep["search_partitions"]),
            shape_buckets=[int(cell.mix["global_batch"])]),
        num_partitions=cell.chips, seed=int(seed))
    reference = cell.plugin("reference", cell.config_name)
    return System(cell, sess, cfg, reference)
