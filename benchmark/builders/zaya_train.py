"""ZAYA1-8B's trainer, built as a user builds it: ``parallax.parallel_run``
on ``models/zaya.build_model`` (a stateful ``Model``: the router's
balancing biases are its ``model_state``), HYBRID plan, nothing reached
around.

The configuration file's ``model`` block holds ``ZayaConfig``'s fields
under their own names (the chip's share of the deployment:
``experts_held`` experts from ``first_expert`` on, the vocabulary
slice); ``deployment`` holds the plan. The weights are made on the
device from ``--seed`` by the engine's own jitted initialiser.

**The biases are brought to rest in set-up** (``System.static_checks``,
the harness's last call before the window; it counts in ``setup_s``):
routing passes over the cell's own batches by the model's own code and
its own rule (``models/zaya.balance_step``, ``bias_update_rate`` a
pass), no weight moving, until over the batches together the fullest
held expert is under ``load_max_over_mean_at_rest`` of the held
experts' mean and the rows routed here are within ``rows_here_at_rest``
of the held share, or ``BALANCE_PASSES_MAX`` passes; the biases then go
into the session (``set_model_state``), and the detail line says
whether they were at rest. A run whose biases are past
``load_max_over_mean_max`` or ``rows_here_max`` after all the passes is
not ``correct``: its rows are the seed's luck again. A
router at its initialisation sends a random half of the experts 4,096
+- 275 of a sequence's 8,192 rows by the luck of which ids it holds
(``PERF.md`` section 4), and a dropless cell's rate follows its rows;
the model's own means against that is its biases, which a deployment's
thousands of steps have long brought to rest and a 10 s window cannot.
Measured (my chip runs, PR 31): under the first trees' schedule (1,000
warm-up steps) eight seeds without the passes spread by 0.62 % (range
0.82 %), over the 0.5 % a rate may depend on its seed, and the same
eight with them by 0.41 %: **the balance did not last that window**,
whose 57 fast steps took an untrained model from a loss of 38 to 9.6
and collapsed the router's deeper layers onto the held experts (4.0 k
rows a layer at its start, 5.5-6.4 k at its end; the median read one of
two levels by when a seed's collapse came). The configuration's
schedule is therefore 20,000 warm-up steps (``assumed.optimizer`` says
why): the weights move little against what the routing reads, the
biases hold the balance (4,046-4,195 rows a layer at the window's end,
the fullest held expert 1.16-1.29 x the held mean) and seven seeds
spread by 0.065 % (``PERF.md`` section 6). What the window ended with
is on the detail line (``window_end``) beside what it started with
(``balance``), and both are held to a limit ((f) below).

The comparison that decides ``correct`` (``reference_check``) runs on
the trained parameters and the biases as the window left them, on two
sequences of the generator's eval stream at the timed length, the
system's own code (bfloat16, the flash kernels, the grouped products'
kernel) against the configuration's plain float32 reference:

(a) the choice: the share of tokens whose expert (the system's own
    ``argmax(p + beta)``) is the reference's, at layer 0 and at the last
    layer, and the largest margin (the reference's best ``p + beta``
    less its second) of a token on which they differ: a disagreement is
    allowed only inside the rounding band. The system's choices are
    read in the run of (b), so the last layer's are those of a stream
    that followed the reference's routing up to there: a token routed
    otherwise at layer 2 is another token at layer 5, on either side;
(b) **under ONE routing**, the reference's choice of every layer fed
    to both sides (``batch["expert_choice"]``; ``ops/moe.routed_experts``
    takes a choice): the negative log-likelihood of every position,
    root mean square of the difference, and the gradients of layer 0's
    ``wq`` and ``conv1_w`` (the grouped convolution's ``A``) and of the
    fullest held expert's ``w_gate`` (by the reference's routing at
    layer 0), and of the tied table, whole (the sum of the lookup's
    scatter-add and the head's ``[V, D]`` product), Frobenius distance
    over the reference's norm, each held to a limit;
(c) every parameter moved by the steps' worth and no more, LEAF BY
    LEAF (``window_change``): the root mean square of a leaf's change
    over the window, over the sum of the rates the schedule gave the
    window's steps (Adam moves an entry by at most about its rate a
    step, whatever the gradient's size; a leaf that starts at 0 has no
    norm to divide by), and the WORST leaf on either side is held: a
    leaf the optimizer never reached reads 0 under any group's norm,
    604 M parameters of experts beside it or not;
(d) the balancing biases, the state no gradient reaches and the
    engine's stateful path carries: after the window they differ from
    what the window started with, and the largest movement is at most
    the window's steps times ``bias_update_rate`` (a step that dropped
    its new state reads 0; a rule applied twice a step is past the
    bound wherever an expert stays on one side of its layer's mean,
    which at rest none does all window: read 0.024-0.045 of 0.057, so
    that fault is ``tests/test_zaya.py``'s, thirteen steps from zero);
(e) ``moe.dropped``, the session's running maximum of the rows routed
    here that no part of ``routed_experts`` covered, is 0;
(f) the balance lasted the window: at its last step the fullest held
    expert is under ``load_max_over_mean_max`` of the held mean and the
    rows routed here within ``rows_here_max`` of the held share, the
    limits a set-up's passes are held to (read 1.16-1.29 x of 2.0 and
    within 2.4 % of 10 %; under the first trees' schedule 3.2-4.9 x and
    35-56 %): a cell whose router collapses inside the window is a
    transient again, and says so.

One negative control in every chip run: the same comparison with the
attention's and the experts' matrices rounded to 8 bits must FAIL (b),
or the tolerances could not see matrix products fed a narrower type
than the configuration states.
"""

from __future__ import annotations

import re

# rounded in the 8-bit control: every matrix of the attention and of
# the experts (``layers/<name>``)
CONTROL_ROUNDS = ("wq", "wk", "wv1", "wv2", "wo", "conv1_w", "w_gate",
                  "w_up", "w_down")
# no more routing passes than this in set-up
BALANCE_PASSES_MAX = 200


def tolerances(cell) -> dict:
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    out = {k: float(tol[k]) for k in (
        "nll_rms_tol", "choice_agree_min", "choice_gap_tol",
        "leaf_change_min", "leaf_change_max",
        "load_max_over_mean_at_rest", "rows_here_at_rest",
        "load_max_over_mean_max", "rows_here_max")}
    out["grad_fro_tol"] = {k: float(v)
                           for k, v in tol["grad_fro_tol"].items()}
    return out


def model_config(cell, **overrides):
    import jax.numpy as jnp
    from parallax_tpu.models import zaya

    m = dict(cell.model)
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    m.update(overrides)
    return zaya.ZayaConfig(num_partitions=cell.chips, **m)


class System:
    def __init__(self, cell, session, cfg, reference, feeds):
        self.cell = cell
        self.session = session
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self._reference = reference
        self._feeds = feeds
        # what the window starts from: the parameters (host copies), the
        # biases and the step counter
        self._before_window = None
        self.balance = None
        # what Keye's builder already has
        self._keye = cell.plugin("builders", "keye_train")

    # -- set-up -----------------------------------------------------------

    def bring_biases_to_rest(self) -> dict:
        """Routing passes over the cell's batches until every batch is
        balanced (the module's docstring); the biases go into the
        session. Returns what the passes saw."""
        import jax
        import numpy as np
        from parallax_tpu.models import zaya
        from parallax_tpu.ops import embedding as emb_ops

        cfg, engine = self.cfg, self.session.engine
        tol = tolerances(self.cell)
        first, held = cfg.first_expert, cfg.experts_held

        @jax.jit
        def route(params, beta, batch):
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                _, s, _ = zaya.forward(cfg, params, beta, batch)
            return zaya.balance_step(cfg, beta, s["load"]), s["load"]

        def reading(load):
            mine = load[:, first:first + held]
            rows = mine.sum(axis=1)
            return (float(np.mean(mine.max(axis=1) * held
                                  / np.maximum(rows, 1.0))),
                    float(np.mean(rows)))

        params = self.session.state.params
        beta = self.session.state.model_state["beta"]
        n = len(self._feeds)
        share = self._feeds[0]["x"].size * held / cfg.num_experts
        readings = [None] * n
        passes, first_readings, at_rest = 0, None, False
        with engine.mesh:
            while passes < BALANCE_PASSES_MAX:
                new_beta, load = route(params, beta, self._feeds[passes % n])
                readings[passes % n] = reading(np.asarray(load))
                passes += 1
                if passes == n:
                    first_readings = list(readings)
                # over the batches together: one batch's readings swing
                # with the rule's own step from pass to pass (its rows by
                # 4 %), and a batch whose commonest ids share an expert
                # keeps that expert near 1.5 x whatever the biases do
                if passes >= n:
                    spread = sum(r[0] for r in readings) / n
                    off = abs(sum(r[1] for r in readings) / (n * share) - 1.0)
                    at_rest = (spread <= tol["load_max_over_mean_at_rest"]
                               and off <= tol["rows_here_at_rest"])
                if at_rest:
                    # the biases these readings were taken under
                    break
                beta = new_beta
        self.session.set_model_state({"beta": beta})
        return {"passes": passes, "at_rest": bool(at_rest),
                # past these the biases have plainly not done their work
                # and the cell's rows are the seed's luck again
                "balanced": bool(spread <= tol["load_max_over_mean_max"]
                                 and off <= tol["rows_here_max"]),
                "held_share_rows": share,
                "load_max_over_mean_by_batch": [r[0] for r in readings],
                "rows_here_by_batch": [r[1] for r in readings],
                "before": {"load_max_over_mean_by_batch":
                           [r[0] for r in first_readings],
                           "rows_here_by_batch":
                           [r[1] for r in first_readings]},
                "bias_abs_max": float(np.max(np.abs(np.asarray(beta))))}

    def static_checks(self) -> list:
        """The tied table in the dense group by the classifier's own
        rule and no table on the slices path; on the chip the attention
        and the experts' products run by their kernels; the compiled
        step holds no array over tokens x experts held x expert width
        and no float32 array over sequence x sequence. Being the
        harness's last call before the window, it also brings the
        biases to rest and copies the parameters to the host for (c)."""
        import jax

        failures = []
        engine = self.session.engine
        spec = engine.plan.var_specs["emb"]
        if spec.is_sparse or spec.reason != "gathered but also used densely":
            failures.append(f"the tied table is classified {spec.kind} "
                            f"({spec.reason})")
        if self.session.state.slice_state:
            failures.append(
                f"tables on the slices path: "
                f"{sorted(self.session.state.slice_state)}")
        index = self.session.layer_index()
        if index is None:
            failures.append("no compiled step to read")
        elif not self.cell.rehearse:
            # off the chip the attention is XLA's einsum and the grouped
            # products XLA's ragged dot
            for layer in ("attention", "moe"):
                if not any(m["opcode"] == "custom-call"
                           and index["layers"][n] == layer
                           for n, m in index["hlo_index"].items()):
                    failures.append(f"no custom call under the scope "
                                    f"`{layer}`: its kernels did not run")
            text = engine.executable_text()
            T = int(self.cell.mix["num_steps"]) \
                * int(self.cell.mix["global_batch"]) // self.cell.chips
            for what, pat in (
                    ("tokens x experts held",
                     rf"\[{T},{self.cfg.experts_held},"
                     rf"{self.cfg.expert_dim}\]"),
                    ("whole float32 scores", rf"f32\[(1,)?{T},{T}\]")):
                if re.search(pat, text):
                    failures.append(f"the compiled step holds an array "
                                    f"over {what}: {pat}")
        self.balance = self.bring_biases_to_rest()
        if not self.balance["balanced"]:
            failures.append(f"the biases did not balance the held experts "
                            f"in {self.balance['passes']} passes")
        state = self.session.state
        self._before_window = jax.device_get(
            {"params": state.params, "beta": state.model_state["beta"],
             "step": state.step})
        return failures

    # -- the system's side of the comparison --------------------------

    def evaluator(self):
        """``evaluate(layers, batch) -> {nll [B, T], grads (the
        reference's ``GRAD_ARRAYS`` and its ``TABLE``), choice [L, N]}``
        by the model's own ``forward`` on the session's parameters
        and biases where the plan placed them, with ``layers`` in place
        of the layer stack; ``batch`` brings the routing
        (``expert_choice``), and ``choice`` is what the system's router
        would have chosen at each layer of that stream."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import zaya
        from parallax_tpu.ops import embedding as emb_ops

        engine = self.session.engine
        params = self.session.state.params
        beta = self.session.state.model_state["beta"]
        names, table = self._reference.GRAD_ARRAYS, self._reference.TABLE
        cfg = self.cfg

        def loss_of(sub, layers, params, beta, batch):
            layers = {**layers, **{k: sub[k] for k in names}}
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                nll, _, picked = zaya.forward(
                    cfg, {**params, table: sub[table], "layers": layers},
                    beta, batch)
            w = batch["w"]
            return jnp.sum(nll * w) / jnp.sum(w), (nll, picked["choice"])

        @jax.jit
        def run(layers, params, beta, batch):
            sub = {table: params[table], **{k: layers[k] for k in names}}
            (_, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                sub, layers, params, beta, batch)
            return aux, grads

        def evaluate(layers, batch):
            with engine.mesh:
                (nll, choice), grads = run(layers, params, beta, batch)
            return {"nll": np.asarray(nll), "choice": np.asarray(choice),
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
                  if k.startswith(("moe.", "router."))}
        tol = tolerances(self.cell)
        state = self.session.state
        params, beta = state.params, state.model_state["beta"]
        moved = window_change(self.cfg, self._before_window,
                              jax.device_get({"params": params, "beta": beta,
                                              "step": state.step}))
        self._before_window = None
        lap("window_change")

        generator = self.cell.plugin("generators",
                                     self.cell.traffic["generator"])
        chips = self.cell.chips
        both = generator.make_eval(self.cell.mix, seed, self.vocab_size,
                                   2 * chips)
        batches = [{k: v[i * chips:(i + 1) * chips] for k, v in both.items()}
                   for i in range(2)]
        layers = params["layers"]
        L = self.cfg.num_layers

        programs = {}
        wants = []
        for b in batches:
            want, grads = self._reference.loss_and_grads(
                params, b, self.cell.model, beta, programs=programs)
            seconds.setdefault("reference_parts", []).append(
                want.pop("seconds"))
            want = {k: np.asarray(v) for k, v in want.items()}
            want["grads"] = {k: np.asarray(v) for k, v in grads.items()}
            wants.append(want)
        lap("reference")
        # ONE routing: the reference's choice of every layer, fed to the
        # system
        routed = [{**b, "expert_choice":
                   w["choice"].reshape(L, *b["x"].shape).astype(np.int32)}
                  for b, w in zip(batches, wants)]
        evaluate = self.evaluator()
        gots = [evaluate(layers, b) for b in routed]
        lap("system")
        first, held = self.cfg.first_expert, self.cfg.experts_held
        # the fullest held expert by the reference's routing at layer 0
        rows = sum(np.bincount(w["choice"][0], minlength=first + held)
                   [first:first + held] for w in wants)
        expert = int(np.argmax(rows))
        out = compare(self._keye, gots, wants, tol, expert)
        lap("compare")
        out["sequences"] = sum(int(b["x"].shape[0]) for b in batches)
        out["tokens"] = sum(int(b["x"].size) for b in batches)
        out["compared_expert"] = first + expert
        out["compared_expert_rows"] = int(rows[expert])
        out["reference_rows_here_by_layer"] = [
            int(sum(np.isin(w["choice"][i], np.arange(first, first + held))
                    .sum() for w in wants)) for i in range(L)]
        out["reference_gate_mean"] = float(
            np.mean([w["gate"].mean() for w in wants]))
        out["polled"] = polled
        out["balance"] = self.balance
        # the last step's, beside the start's: a balance that does not
        # last the window shows here (the module's docstring)
        end = window_end(polled, self.balance["held_share_rows"], tol)
        out["window_end"] = end
        dropped = polled.get("moe.dropped")
        out["moe_dropped"] = dropped
        out["window_change"] = moved
        low, high = moved["leaf_change_least"], moved["leaf_change_most"]
        change_ok = (tol["leaf_change_min"] <= low[1]
                     and high[1] <= tol["leaf_change_max"])
        out["ok"] = bool(out["ok"] and dropped == 0 and change_ok
                         and moved["biases_ok"] and end["held"])

        def to_8bit(x):
            # 1 sign, 3 mantissa bits and the exponent's full range (a
            # pair of casts the TPU compiler would remove as excess
            # precision; ``reduce_precision`` it must keep)
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=3)

        rounded = {k: to_8bit(v) if k in CONTROL_ROUNDS else v
                   for k, v in layers.items()}
        control = compare(self._keye, [evaluate(rounded, b) for b in routed],
                          wants, tol, expert)
        lap("control_8bit")
        out["control_8bit"] = {
            "rounded": list(CONTROL_ROUNDS),
            "nll_rms_err": control["nll_rms_err"],
            "grad_fro_err": control["grad_fro_err"],
            "caught": not control["precision_ok"]}
        # the key the harness's rehearsal test reads off every cell's
        # detail line; here it holds the attention's and the experts'
        # matrices in 8 bits
        out["control_lstm_weights_8bit"] = out["control_8bit"]
        out["seconds"] = seconds
        # at the rehearsal's sizes the control proves nothing about the
        # chip's tolerances: it is reported there, and decides only a
        # chip run
        if not self.cell.rehearse:
            out["ok"] = bool(out["ok"] and out["control_8bit"]["caught"])
        return out


def compare(keye, gots: list, wants: list, tol: dict, expert: int) -> dict:
    """The system's outputs against the reference's on the eval batches
    (the module's docstring, (a) and (b)); ``expert`` is the held expert
    whose ``w_gate`` gradient is compared. ``precision_ok`` holds (b),
    which the 8-bit control must fail; ``ok`` holds (a) too."""
    import numpy as np

    d = np.concatenate([g["nll"].astype(np.float64) - w["nll"]
                        for g, w in zip(gots, wants)])
    nll_rms = float(np.sqrt(np.mean(d * d)))
    grad_fro = {"wq": keye._fro(gots, wants, "wq"),
                "conv1_w": keye._fro(gots, wants, "conv1_w"),
                "w_gate": keye._fro(gots, wants, "w_gate", expert),
                "emb": table_fro(gots, wants)}
    out = {"system_nll": float(np.mean([g["nll"] for g in gots])),
           "reference_nll": float(np.mean([w["nll"] for w in wants])),
           "nll_rms_err": nll_rms,
           "nll_max_err": float(np.max(np.abs(d))),
           "grad_fro_err": grad_fro, **tol}
    out["precision_ok"] = bool(
        nll_rms <= tol["nll_rms_tol"]
        and all(grad_fro[k] <= t for k, t in tol["grad_fro_tol"].items()))
    # (a) the choice at the first and the last layer
    agree, gap = {}, {}
    last = wants[0]["choice"].shape[0] - 1
    for name, i in (("layer0", 0), ("last", last)):
        differ = np.concatenate([g["choice"][i] != w["choice"][i]
                                 for g, w in zip(gots, wants)])
        margin = np.concatenate([w["margin"][i] for w in wants])
        agree[name] = float(1.0 - differ.mean())
        gap[name] = float(margin[differ].max()) if differ.any() else 0.0
    out["choice_agree_share"], out["choice_gap_max"] = agree, gap
    out["ok"] = bool(
        out["precision_ok"]
        and min(agree.values()) >= tol["choice_agree_min"]
        and max(gap.values()) <= tol["choice_gap_tol"])
    return out


def table_fro(gots: list, wants: list) -> float:
    """Frobenius distance of the tied table's gradient, summed over the
    eval batches, over the reference's norm."""
    import numpy as np
    ref = sum(w["grads"]["emb"].astype(np.float64) for w in wants)
    got = sum(g["grads"]["emb"].astype(np.float64) for g in gots)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def window_end(polled: dict, held_share_rows: float, tol: dict) -> dict:
    """The balance at the window's last step, off the polled gauges, and
    whether the biases have held what the passes brought (the module's
    docstring, (f))."""
    rows = polled.get("moe.rows_here")
    spread = polled.get("moe.load_max_over_mean")
    over = None if rows is None else rows / held_share_rows
    return {"rows_here": rows, "rows_here_over_held_share": over,
            "load_max_over_mean": spread,
            "held": bool(over is not None and spread is not None
                         and spread <= tol["load_max_over_mean_max"]
                         and abs(over - 1.0) <= tol["rows_here_max"])}


def window_change(cfg, before: dict, after: dict) -> dict:
    """What the window's steps did to the state (host copies of
    ``params``, ``beta`` and ``step`` at its two ends; the module's
    docstring, (c) and (d))."""
    import jax
    import numpy as np
    from parallax_tpu.models import zaya

    first, last = int(before["step"]), int(after["step"])
    rate = zaya.scheduled_rate(cfg)
    rates = [float(rate(t)) if callable(rate) else float(rate)
             for t in range(first, last)]
    leaves = {}
    for (path, b), a in zip(
            jax.tree_util.tree_leaves_with_path(before["params"]),
            jax.tree_util.tree_leaves(after["params"])):
        rms = np.sqrt(np.mean(np.square(a - b, dtype=np.float64)))
        leaves["/".join(k.key for k in path)] = float(rms / sum(rates))
    by_change = sorted(leaves, key=leaves.get)
    beta_moved = float(np.max(np.abs(
        after["beta"].astype(np.float64) - before["beta"])))
    steps_worth = (last - first) * cfg.bias_update_rate
    return {"steps": last - first, "first_step": first,
            "rate_sum": sum(rates), "leaf_change": leaves,
            "leaf_change_least": [by_change[0], leaves[by_change[0]]],
            "leaf_change_most": [by_change[-1], leaves[by_change[-1]]],
            "beta_moved_max": beta_moved,
            "beta_moved_steps_worth": steps_worth,
            # float32 sums of ``steps`` equal steps: a part in 1e4 of room
            "biases_ok": bool(0.0 < beta_moved <= steps_worth * 1.0001)}


def build(cell, seed: int) -> System:
    import parallax_tpu as parallax
    from parallax_tpu.models import zaya

    cfg = model_config(cell)
    dep = cell.deployment
    sess, *_ = parallax.parallel_run(
        zaya.build_model(cfg),
        parallax_config=parallax.Config(
            run_option=dep["run_option"],
            search_partitions=bool(dep["search_partitions"]),
            shape_buckets=[int(cell.mix["global_batch"])]),
        num_partitions=cell.chips, seed=int(seed))
    generator = cell.plugin("generators", cell.traffic["generator"])
    # the batches the window cycles through, for the balancing passes
    # (the kind's loop makes the same ones from the same seed)
    feeds = generator.make(cell.mix, seed=seed, vocab_size=cfg.vocab_size)
    reference = cell.plugin("reference", cell.config_name)
    return System(cell, sess, cfg, reference, feeds)
