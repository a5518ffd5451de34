"""Olmo-Hybrid-7B's trainer, built as a user builds it:
``parallax.parallel_run`` on ``models/olmo_hybrid.build_model``, HYBRID
plan with the embedding on the slices path, nothing reached around.

The configuration file's ``model`` block holds ``OlmoHybridConfig``'s
fields under their own names, but for the chip's share of the heads:
``attn_units_held`` (a unit is one head of either mixer; the benchmark's
contract refuses a ``reduced`` key with ``head`` in it), which
``model_config`` maps onto the program's ``heads_held``; the block's
``num_heads`` and ``num_kv_heads`` are the HELD ones (what the kernels
run and the flash roofline readers read), the layer's own count is
theirs times ``deployment.chips_sharing_a_layer``. The weights are made
on the device from ``--seed`` by the engine's own jitted initialiser.

The comparison that decides ``correct`` (``reference_check``) runs on
the parameters as the window left them, on two sequences of the
generator's eval stream at the timed length, the system's own code
(bfloat16, the chunked rule's kernels, the flash kernels) against the
configuration's plain float32 reference, whose rule runs token by token:

(a) the negative log-likelihood of every position, root mean square of
    the difference, and the gradients of a linear layer's ``wq``, of its
    ``wa`` (the decay's path), of its key convolution, of the full
    layer's ``wq`` and of its MLP's ``w_gate``, and of the table,
    Frobenius distance over the reference's norm, each held to a limit;
(b) every parameter moved by the steps' worth and no more, LEAF BY LEAF
    (``builders/mellum_train.window_change``).

Two negative controls in every chip run, each of which must FAIL (a).
The same comparison with every matrix of the mixers and the MLPs rounded
to 8 bits (``control_8bit``), or the tolerances could not see matrix
products fed a narrower type than the configuration states. And the
system against **the reference with the decay held at 1 and beta
without its factor 2** (``control_no_gates``: the same compiled
reference, other ``gates``): a program that dropped the gate or the
negative eigenvalues would agree with THAT reference, so the limits must
tell the two apart.
"""

from __future__ import annotations

import re

# rounded in the 8-bit control: every matrix of both stacks
CONTROL_ROUNDS = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")
# the compared gradients: the result's key, the reference's leaf and the
# part of the stacked leaf that is compared (None: whole)
COMPARED = (("wq_linear", "linear/wq", (0, 0)),
            ("wa", "linear/wa", (0, 0)),
            ("conv_k", "linear/conv_k", (0, 0)),
            ("wq_full", "full/wq", (0,)),
            ("w_gate", "full/w_gate", (0,)),
            ("emb", "emb", None))


def tolerances(cell) -> dict:
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    out = {k: float(tol[k]) for k in ("nll_rms_tol", "leaf_change_min",
                                      "leaf_change_max")}
    out["grad_fro_tol"] = {k: float(v)
                           for k, v in tol["grad_fro_tol"].items()}
    return out


def model_config(cell):
    import jax.numpy as jnp
    from parallax_tpu.models import olmo_hybrid

    m = dict(cell.model)
    held = int(m.pop("attn_units_held"))
    if not held == m["num_heads"] == m.pop("num_kv_heads"):
        raise ValueError("the block's num_heads and num_kv_heads are the "
                         "held ones: both follow from attn_units_held")
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    for key in ("layer_types", "flash_tiles"):
        m[key] = tuple(m[key])
    m["num_heads"] = held * int(cell.deployment["chips_sharing_a_layer"])
    return olmo_hybrid.OlmoHybridConfig(
        num_partitions=cell.chips, heads_held=held, **m)


class System:
    def __init__(self, cell, session, cfg, reference, feeds):
        self.cell = cell
        self.session = session
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self._reference = reference
        self._feeds = feeds
        # what the window starts from: the parameters (host copies) and
        # the step counter
        self._before_window = None
        self._mellum = cell.plugin("builders", "mellum_train")

    def static_checks(self) -> list:
        """The embedding on the slices path; on the chip the full
        layer's attention and the rule run by their kernels; the
        compiled step holds no float32 array over sequence x sequence.
        Being the harness's last call before the window, it also copies
        the parameters to the host for (b)."""
        import jax

        failures = []
        tables = sorted(self.session.state.slice_state or ())
        if tables != ["emb"]:
            failures.append(f"the embedding is not on the slices path "
                            f"(slice tables: {tables})")
        index = self.session.layer_index()
        if index is None:
            failures.append("no compiled step to read")
        elif not self.cell.rehearse:
            # off the chip the attention is XLA's einsum and the rule
            # the chunked algebra under XLA's scan
            for layer in ("attention", "delta_rule"):
                if not any(m["opcode"] == "custom-call"
                           and index["layers"][n] == layer
                           for n, m in index["hlo_index"].items()):
                    failures.append(f"no custom call under the scope "
                                    f"`{layer}`: its kernels did not run")
            T = int(self.cell.mix["num_steps"]) \
                * int(self.cell.mix["global_batch"]) // self.cell.chips
            pat = rf"f32\[(1,)?{T},{T}\]"
            if re.search(pat, self.session.engine.executable_text()):
                failures.append(f"the compiled step holds whole float32 "
                                f"scores: {pat}")
        state = self.session.state
        self._before_window = jax.device_get(
            {"params": state.params, "step": state.step})
        return failures

    # -- the system's side of the comparison --------------------------

    def evaluator(self):
        """``evaluate(params, batch) -> {nll [B, T], grads (the
        reference's ``compared`` leaves)}`` by the model's own
        ``forward`` on ``params`` where the plan placed them."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import olmo_hybrid
        from parallax_tpu.ops import embedding as emb_ops

        engine = self.session.engine
        ref, cfg = self._reference, self.cfg

        def loss_of(sub, params, batch):
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                nll, _ = olmo_hybrid.forward(
                    cfg, ref.with_compared(params, sub), batch)
            w = batch["w"]
            return jnp.sum(nll * w) / jnp.sum(w), nll

        @jax.jit
        def run(params, batch):
            (_, nll), grads = jax.value_and_grad(loss_of, has_aux=True)(
                ref.compared(params), params, batch)
            return nll, grads

        def evaluate(params, batch):
            with engine.mesh:
                nll, grads = run(params, batch)
            return {"nll": np.asarray(nll),
                    "grads": {k: np.asarray(v) for k, v in grads.items()}}

        return evaluate

    def reference_check(self, seed: int) -> dict:
        import time

        import jax
        import numpy as np

        clock = [time.perf_counter()]
        seconds = {}

        def lap(name):
            clock.append(time.perf_counter())
            seconds[name] = round(clock[-1] - clock[-2], 2)

        # the last step's outputs as the session polled them
        polled = {k: v for k, v in self.session.metrics_snapshot().items()
                  if k.startswith("linear_attn.")}
        tol = tolerances(self.cell)
        state = self.session.state
        params = state.params
        moved = self._mellum.window_change(
            self.cfg, self._before_window,
            jax.device_get({"params": params, "step": state.step}),
            np.unique(np.concatenate([f["x"].ravel()
                                      for f in self._feeds])))
        self._before_window = None
        lap("window_change")

        generator = self.cell.plugin("generators",
                                     self.cell.traffic["generator"])
        chips = self.cell.chips
        both = generator.make_eval(self.cell.mix, seed, self.vocab_size,
                                   2 * chips)
        batches = [{k: v[i * chips:(i + 1) * chips] for k, v in both.items()}
                   for i in range(2)]
        programs = {}

        def reference(batch, gates=None):
            want, grads = self._reference.loss_and_grads(
                params, batch, self.cell.model, gates, programs=programs)
            seconds.setdefault("reference_parts", []).append(
                want.pop("seconds"))
            want = {k: np.asarray(v) for k, v in want.items()}
            want["grads"] = {k: np.asarray(v) for k, v in grads.items()}
            return want

        wants = [reference(b) for b in batches]
        lap("reference")
        evaluate = self.evaluator()
        gots = [evaluate(params, b) for b in batches]
        lap("system")
        out = compare(gots, wants, tol)
        out["sequences"] = sum(int(b["x"].shape[0]) for b in batches)
        out["tokens"] = sum(int(b["x"].size) for b in batches)
        out["reference_decay_mean"] = float(np.mean(
            [w["decay_mean"] for w in wants]))
        out["reference_beta_mean"] = float(np.mean(
            [w["beta_mean"] for w in wants]))
        out["polled"] = polled
        out["window_change"] = moved
        low, high = moved["leaf_change_least"], moved["leaf_change_most"]
        change_ok = (tol["leaf_change_min"] <= low[1]
                     and high[1] <= tol["leaf_change_max"])
        out["ok"] = bool(out["ok"] and change_ok)

        def to_8bit(x):
            # 1 sign, 3 mantissa bits and the exponent's full range (a
            # pair of casts the TPU compiler would remove as excess
            # precision; ``reduce_precision`` it must keep)
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=3)

        rounded = {**params, **{
            stack: {k: to_8bit(v) if k in CONTROL_ROUNDS else v
                    for k, v in params[stack].items()}
            for stack in ("linear", "full")}}
        control = compare([evaluate(rounded, b) for b in batches], wants,
                          tol)
        del rounded
        lap("control_8bit")
        out["control_8bit"] = {
            "rounded": list(CONTROL_ROUNDS),
            "nll_rms_err": control["nll_rms_err"],
            "grad_fro_err": control["grad_fro_err"],
            "caught": not control["ok"]}
        # the key the harness's rehearsal test reads off every cell's
        # detail line; here it holds the mixers' and the MLPs' matrices
        # in 8 bits
        out["control_lstm_weights_8bit"] = out["control_8bit"]
        # the system as it is against a model WITHOUT its gates
        blind = self._reference.model_gates(self.cell.model,
                                            without_gates=True)
        control = compare(gots, [reference(b, blind) for b in batches], tol)
        lap("control_no_gates")
        out["control_no_gates"] = {
            "reference": "the decay held at 1, beta without its factor 2",
            "nll_rms_err": control["nll_rms_err"],
            "grad_fro_err": control["grad_fro_err"],
            "caught": not control["ok"]}
        out["seconds"] = seconds
        # at the rehearsal's sizes the controls prove nothing about the
        # chip's tolerances: they are reported there, and decide only a
        # chip run
        if not self.cell.rehearse:
            out["ok"] = bool(out["ok"] and out["control_8bit"]["caught"]
                             and out["control_no_gates"]["caught"])
        return out


def _fro(gots, wants, name, at) -> float:
    """Frobenius distance of the gradient ``name`` (its part ``at``,
    None: whole), summed over the eval batches, over the reference's
    norm; None where the reference's gradient is zero (``wa`` with the
    decay held)."""
    import numpy as np

    def part(side):
        g = side["grads"][name].astype(np.float64)
        return g if at is None else g[at]

    ref = sum(part(w) for w in wants)
    got = sum(part(g) for g in gots)
    norm = np.linalg.norm(ref)
    return float(np.linalg.norm(got - ref) / norm) if norm else None


def compare(gots: list, wants: list, tol: dict) -> dict:
    """The system's outputs against the reference's on the eval batches
    (the module's docstring, (a)); ``ok`` holds every limit of it, and
    both controls must lose it."""
    import numpy as np

    d = np.concatenate([g["nll"].astype(np.float64) - w["nll"]
                        for g, w in zip(gots, wants)])
    nll_rms = float(np.sqrt(np.mean(d * d)))
    grad_fro = {key: _fro(gots, wants, name, at)
                for key, name, at in COMPARED}
    out = {"system_nll": float(np.mean([g["nll"] for g in gots])),
           "reference_nll": float(np.mean([w["nll"] for w in wants])),
           "nll_rms_err": nll_rms,
           "nll_max_err": float(np.max(np.abs(d))),
           "grad_fro_err": grad_fro, **tol}
    out["ok"] = bool(
        nll_rms <= tol["nll_rms_tol"]
        and all(grad_fro[k] is not None and grad_fro[k] <= t
                for k, t in tol["grad_fro_tol"].items()))
    return out


def build(cell, seed: int) -> System:
    import parallax_tpu as parallax
    from parallax_tpu.models import olmo_hybrid

    cfg = model_config(cell)
    dep = cell.deployment
    sess, *_ = parallax.parallel_run(
        olmo_hybrid.build_model(cfg),
        parallax_config=parallax.Config(
            run_option=dep["run_option"], sparse_grad_mode="slices",
            search_partitions=bool(dep["search_partitions"]),
            shape_buckets=[int(cell.mix["global_batch"])]),
        num_partitions=cell.chips, seed=int(seed))
    generator = cell.plugin("generators", cell.traffic["generator"])
    # the batches the window cycles through: which rows of the table it
    # feeds (the kind's loop makes the same ones from the same seed)
    feeds = generator.make(cell.mix, seed=seed, vocab_size=cfg.vocab_size)
    reference = cell.plugin("reference", cell.config_name)
    return System(cell, sess, cfg, reference, feeds)
