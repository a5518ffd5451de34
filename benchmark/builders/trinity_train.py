"""Trinity-Mini's trainer, built as a user builds it:
``parallax.parallel_run`` on ``models/trinity.build_model`` (a stateful
``Model``: the router's balancing biases are its ``model_state``), HYBRID
plan with the embedding on the slices path, nothing reached around.

The configuration file's ``model`` block holds ``TrinityConfig``'s
fields under their own names (the chip's share of the deployment:
``experts_held`` experts from ``first_expert`` on, the vocabulary slice,
one leading dense layer and one period of the expert layers);
``deployment`` holds the plan. The weights are made on the device from
``--seed`` by the engine's own jitted initialiser; the router starts as
Keye's does (``builders/keye_train.router_in_copies``: one chip's range
of 16 columns and a permuted, noised copy for each of the eight chips'
ranges), so that the rows routed here stay near the balanced share from
seed to seed.

**The biases are brought to rest in set-up** (``System.before_window``,
which ``static_checks`` ends with: the harness has no other call between
the warm steps and the window, ``builders/zaya_train`` does the same; it
counts in ``setup_s``): ``BALANCE_PASSES`` routing passes over the
cell's own batches by the model's own code and its own rule
(``ops/moe.balance_step``, ``load_balance_coeff`` a pass), no weight
moving. The number of passes is FIXED, the same for every seed, so that
``setup_s`` is one number a cell; a run whose biases after them leave the
fullest held expert over ``load_max_over_mean_max`` of the held experts'
mean over the four batches together, or the rows routed here further
than ``rows_here_max`` from the held share, is not ``correct``. Why
(``PERF.md`` section 6, PR 39; my chip runs): the router's eight copies
hold the ROWS routed here near the balanced 8,192 (7,887-8,438 at the
first step over ten seeds), but the commonest ids of a Zipf(1.05)
sequence choose alike and at the initialiser the fullest held expert has
3.7-6.8 x the held mean; the rule takes 120-210 steps of 0.001 to level
that, a deployment's first minutes, and a 10 s window of 38 steps would
be that transient. The RATE never needed the passes: eight seeds without
them read 29,460-29,503 tokens/s/chip, a spread of 0.09 %.

The comparison that decides ``correct`` (``reference_check``) runs on
the parameters and the biases as the window left them, on two sequences
of the generator's eval stream at the timed length, the system's own
code (bfloat16, the flash kernels of BOTH kinds under the scan's
``cond``, the grouped products' kernel) against the configuration's
plain float32 reference:

(a) the experts of a token, the system's own top-8 of ``s + b`` on the
    stream the reference's routing made: the share of (token, expert)
    places on which the two agree, at the first and at the last expert
    layer, and the largest distance of a disputed expert's biased score
    from that token's eighth: a disagreement is allowed only inside the
    rounding band;
(b) **under ONE routing**, the reference's top-8 of every expert layer
    fed to both sides (``batch["expert_choice"]``): the negative
    log-likelihood of every position, root mean square of the
    difference, and the gradients of ``wq`` in the first sliding expert
    layer AND in the full one, of the attention's gate (every expert
    layer's), of the fullest held expert's ``w_gate`` (by the
    reference's routing at the first expert layer), of the shared
    expert's gate matrix and of the router (every expert layer's), of
    the dense layer's ``w_up`` and of the table, Frobenius distance over
    the reference's norm, each held to a limit;
(c) every parameter moved by the steps' worth and no more, LEAF BY LEAF
    (``builders/mellum_train.window_change``), the WORST leaf on either
    side held; and the biases, the state no gradient reaches: they
    moved, by no more than the window's steps times
    ``load_balance_coeff``;
(d) ``moe.dropped``, the session's running maximum of the rows routed
    here that no part of ``routed_experts`` covered, is 0;
(e) the balance lasted the window (``System.balance_at_the_end``): ONE
    routing pass over the window's four batches on the parameters and
    the biases as the window left them (a single batch's fullest expert
    swings with its commonest id and cannot tell a levelled routing from
    an unlevelled one; the four together can), held by
    ``builders/zaya_train.window_end`` to the same two limits; and the
    same pass under biases of ZERO, the routing the rule never touched,
    reported beside it (``control_no_biases``: the limit lies between
    the two readings; this control does not decide, because what it
    reads is the luck of the seed's initialiser: 3.4-6.7 x).

Two negative controls in every chip run, each of which must FAIL (b).
The same comparison with the attention's, the MLP's and the experts'
matrices rounded to 8 bits INSIDE the evaluator's program (no second
copy of them lives beside the session's state), or the tolerances could
not see matrix products fed a narrower type than the configuration
states. And the system against **the reference read as another model's
block** (``control_other_block``: the same compiled reference under
other tables: no gate on the attention, RoPE on the full layer too, a
softmax router whose gates sum to one, no shared expert): a program
that left out what this configuration adds would agree with THAT
reference, so the limits must tell the two apart.
"""

from __future__ import annotations

import re

# rounded in the 8-bit control: every matrix of the attention, of the
# dense MLP and of the experts (``dense/<name>``, ``layers/<name>``)
CONTROL_ROUNDS = ("wq", "wk", "wv", "w_attn_gate", "wo", "w_gate", "w_up",
                  "w_down", "shared_w_gate", "shared_w_up", "shared_w_down")
# the independent noise on every router column (Keye's, and why)
ROUTER_COPY_NOISE = 0.15
# set-up's routing passes, the same for every seed: forty rounds of the
# cell's four batches. Of eleven seeds the slowest was under 2.15 x by
# pass 160 (2.10 at 210), the others under 2.1 by 115-130; seven more
# read 1.80-2.08 after the 160 (PERF.md section 6, PR 39)
BALANCE_PASSES = 160
SLIDING, FULL = "sliding_attention", "full_attention"


def tolerances(cell) -> dict:
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    out = {k: float(tol[k]) for k in (
        "nll_rms_tol", "expert_agree_min", "expert_gap_tol",
        "leaf_change_min", "leaf_change_max",
        "load_max_over_mean_max", "rows_here_max")}
    out["grad_fro_tol"] = {k: float(v)
                           for k, v in tol["grad_fro_tol"].items()}
    return out


def model_config(cell):
    import jax.numpy as jnp
    from parallax_tpu.models import trinity

    m = dict(cell.model)
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    for key in ("layer_types", "flash_tiles"):
        m[key] = tuple(m[key])
    return trinity.TrinityConfig(num_partitions=cell.chips, **m)


class System:
    def __init__(self, cell, session, cfg, reference, feeds):
        self.cell = cell
        self.session = session
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self._reference = reference
        self._feeds = feeds
        # what the window starts from: the parameters and the biases
        # (host copies) and the step counter; what set-up's routing
        # passes saw
        self._before_window = None
        self.balance = None
        self._route = None
        # the first expert layer of each kind: whose `wq` gradients are
        # compared
        kinds = cfg.kinds[cfg.num_dense_layers:]
        self.compared = {"wq_sliding": kinds.index(SLIDING),
                         "wq_full": kinds.index(FULL)}
        # what Keye's, ZAYA's and Mellum2's builders already have
        self._keye = cell.plugin("builders", "keye_train")
        self._zaya = cell.plugin("builders", "zaya_train")
        self._mellum = cell.plugin("builders", "mellum_train")

    def _state(self) -> dict:
        import jax
        state = self.session.state
        return jax.device_get(
            {"params": state.params, "step": state.step,
             "router_bias": state.model_state["router_bias"]})

    def router(self):
        """``route(params, bias, batch) -> (the biases after the rule's
        step, load [L_moe, E])`` by the model's own forward, jitted
        once, and ``readings(loads)``: over the given passes together,
        the layers' mean of the fullest held expert over the held
        experts' mean, and the rows routed here a layer over the held
        share."""
        import jax
        import numpy as np
        from parallax_tpu.models import trinity
        from parallax_tpu.ops import embedding as emb_ops
        from parallax_tpu.ops import moe as moe_ops

        cfg, engine = self.cfg, self.session.engine
        first, held = cfg.first_expert, cfg.experts_held
        share = self._feeds[0]["x"].size * cfg.experts_per_token * held \
            / cfg.num_experts

        if self._route is None:
            @jax.jit
            def route(params, bias, batch):
                with emb_ops.sharded_lookup_scope(
                        engine.mesh, engine.plan.sharded_shapes):
                    _, s, _ = trinity.forward(cfg, params, bias, batch)
                return moe_ops.balance_step(
                    bias, s["load"], cfg.load_balance_coeff), s["load"]
            self._route = route

        def readings(loads) -> dict:
            mine = np.stack([np.asarray(load)[:, first:first + held]
                             for load in loads])        # [passes, L, held]
            rows = mine.sum(axis=-1)
            spread = np.mean(mine.max(axis=-1) * held
                             / np.maximum(rows, 1.0), axis=-1)
            return {"load_max_over_mean": float(np.mean(spread)),
                    "rows_here": float(np.mean(rows)),
                    "load_max_over_mean_by_batch": spread.tolist(),
                    "rows_here_by_batch": np.mean(rows, axis=-1).tolist(),
                    "held_share_rows": share}

        return self._route, readings

    def held(self, reading: dict) -> dict:
        """A reading of ``router``'s against the cell's two limits, by
        ``builders/zaya_train.window_end``."""
        return self._zaya.window_end(
            {"moe.rows_here": reading["rows_here"],
             "moe.load_max_over_mean": reading["load_max_over_mean"]},
            reading["held_share_rows"], tolerances(self.cell))

    def bring_biases_to_rest(self) -> dict:
        """``BALANCE_PASSES`` routing passes over the cell's batches by
        the model's own code and its own rule (``ops/moe.balance_step``
        at ``load_balance_coeff`` a pass), no weight moving; the biases
        then go into the session. Returns what the first and the last
        round of the batches read, and every tenth round between."""
        import numpy as np

        engine = self.session.engine
        route, readings = self.router()
        params = self.session.state.params
        bias = self.session.state.model_state["router_bias"]
        n = len(self._feeds)
        rounds = []
        with engine.mesh:
            for _ in range(BALANCE_PASSES // n):
                loads = []
                for feed in self._feeds:
                    bias, load = route(params, bias, feed)
                    loads.append(load)
                rounds.append(loads)
        self.session.set_model_state({"router_bias": bias})
        host = np.asarray(bias)
        before, after = readings(rounds[0]), readings(rounds[-1])
        return {"passes": len(rounds) * n, "at_rest": self.held(after)["held"],
                **after, "before": before,
                "every_tenth_round": [
                    [i * n, round(readings(r)["load_max_over_mean"], 3)]
                    for i, r in enumerate(rounds) if i % 10 == 0],
                "bias_spread": float(np.mean(host.max(axis=1)
                                             - host.min(axis=1)))}

    def balance_at_the_end(self) -> dict:
        """(e): one routing pass over the window's batches on the
        parameters and the biases as the window left them, and the same
        pass under biases of zero (the control, which is reported and
        does not decide). No state moves."""
        import jax.numpy as jnp

        route, readings = self.router()
        state = self.session.state
        bias = state.model_state["router_bias"]
        with self.session.engine.mesh:
            end, unlevelled = (
                readings([route(state.params, b, feed)[1]
                          for feed in self._feeds])
                for b in (bias, jnp.zeros_like(bias)))
        return {**end, **self.held(end),
                "control_no_biases": {
                    **unlevelled,
                    "caught": not self.held(unlevelled)["held"]}}

    def _gauges(self) -> dict:
        """The last step's outputs as the session polled them."""
        return {k: v for k, v in self.session.metrics_snapshot().items()
                if k.startswith(("moe.", "router."))}

    def static_checks(self) -> list:
        """The embedding on the slices path; on the chip the attention of
        both kinds and the experts' products run by their kernels; the
        compiled step holds no array over tokens x experts held x expert
        width and no float32 array over sequence x sequence. Being the
        harness's last call before the window, it ends with
        ``before_window``."""
        failures = []
        engine = self.session.engine
        tables = sorted(self.session.state.slice_state or ())
        if tables != ["emb"]:
            failures.append(f"the embedding is not on the slices path "
                            f"(slice tables: {tables})")
        index = self.session.layer_index()
        if index is None:
            failures.append("no compiled step to read")
        elif not self.cell.rehearse:
            # off the chip the attention is XLA's einsum under a traced
            # window and the grouped products XLA's ragged dot
            for layer in ("attention", "window_attention", "moe"):
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
        return failures + self.before_window()

    def before_window(self) -> list:
        """What the window starts from: the biases brought to rest (the
        one change of state set-up makes after the warm steps), then the
        parameters and the biases copied to the host for (c)."""
        self.balance = self.bring_biases_to_rest()
        self._before_window = self._state()
        if self.balance["at_rest"]:
            return []
        return [f"{self.balance['passes']} passes left the fullest held "
                f"expert at {self.balance['load_max_over_mean']:.2f} x the "
                f"held mean and {self.balance['rows_here']:.0f} rows here"]

    # -- the system's side of the comparison --------------------------

    def evaluator(self):
        """``evaluate(batch, rounded) -> {nll [B, T], grads (the
        reference's ``compared`` leaves), expert_choice [L_moe, N, k]}``
        by the model's own ``forward`` on the session's parameters and
        biases where the plan placed them; ``batch`` brings the routing
        (``expert_choice``), and the returned choice is what the
        system's router would have chosen at each expert layer of that
        stream. With ``rounded`` every matrix of ``CONTROL_ROUNDS`` is
        rounded to 8 bits on its way into the cast the forward makes
        anyway: one program serves the comparison and its control, and
        no second copy of the matrices is made."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import trinity
        from parallax_tpu.ops import embedding as emb_ops

        engine = self.session.engine
        state = self.session.state
        params, bias = state.params, state.model_state["router_bias"]
        ref, cfg = self._reference, self.cfg

        def to_8bit(x, rounded):
            # 1 sign, 3 mantissa bits and the exponent's full range (a
            # pair of casts the TPU compiler would remove as excess
            # precision; ``reduce_precision`` it must keep); the
            # gradient passes as if the leaf had come rounded
            low = jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=3)
            return x + jax.lax.stop_gradient(
                jnp.where(rounded, low - x, jnp.zeros((), x.dtype)))

        def loss_of(sub, params, bias, batch, rounded):
            params = ref.with_compared(params, sub)
            for stack in ("dense", "layers"):
                if stack in params:
                    params[stack] = {
                        k: to_8bit(v, rounded) if k in CONTROL_ROUNDS else v
                        for k, v in params[stack].items()}
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                nll, _, choice = trinity.forward(cfg, params, bias, batch)
            w = batch["w"]
            return jnp.sum(nll * w) / jnp.sum(w), (nll, choice)

        @jax.jit
        def run(params, bias, batch, rounded):
            (_, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                ref.compared(params), params, bias, batch, rounded)
            return aux, grads

        def evaluate(batch, rounded=False):
            with engine.mesh:
                (nll, choice), grads = run(params, bias, batch,
                                           jnp.asarray(bool(rounded)))
            return {"nll": np.asarray(nll),
                    "expert_choice": np.asarray(choice),
                    "grads": {k: np.asarray(v) for k, v in grads.items()}}

        return evaluate

    def reference_check(self, seed: int) -> dict:
        import time

        import numpy as np

        clock = [time.perf_counter()]
        seconds = {}

        def lap(name):
            clock.append(time.perf_counter())
            seconds[name] = round(clock[-1] - clock[-2], 2)

        polled = self._gauges()
        end = self.balance_at_the_end()
        lap("balance_at_the_end")
        tol = tolerances(self.cell)
        cfg = self.cfg
        state = self.session.state
        params, bias = state.params, state.model_state["router_bias"]
        before, after = self._before_window, self._state()
        self._before_window = None
        moved = self._mellum.window_change(
            cfg, before, after,
            np.unique(np.concatenate([f["x"].ravel()
                                      for f in self._feeds])))
        table_change(cfg, moved)
        moved.update(biases_change(cfg, before, after))
        lap("window_change")

        generator = self.cell.plugin("generators",
                                     self.cell.traffic["generator"])
        chips = self.cell.chips
        both = generator.make_eval(self.cell.mix, seed, self.vocab_size,
                                   2 * chips)
        batches = [{k: v[i * chips:(i + 1) * chips] for k, v in both.items()}
                   for i in range(2)]
        L, k = cfg.num_moe_layers, cfg.experts_per_token

        programs = {}

        def reference(batch, tables=None):
            want, grads = self._reference.loss_and_grads(
                params, bias, batch, self.cell.model, tables,
                programs=programs)
            seconds.setdefault("reference_parts", []).append(
                want.pop("seconds"))
            want = {k: np.asarray(v) for k, v in want.items()}
            want["grads"] = {k: np.asarray(v) for k, v in grads.items()}
            return want

        wants = [reference(b) for b in batches]
        lap("reference")
        # ONE routing: the reference's top-k of every expert layer, fed
        # to the system (and to the control's reference)
        routed = [{**b, "expert_choice": w["expert_choice"].reshape(
                       L, *b["x"].shape, k).astype(np.int32)}
                  for b, w in zip(batches, wants)]
        evaluate = self.evaluator()
        gots = [evaluate(b) for b in routed]
        lap("system")
        first, held = cfg.first_expert, cfg.experts_held
        # the fullest held expert by the reference's routing at the
        # first expert layer
        rows = sum(np.bincount(w["expert_choice"][0].ravel(),
                               minlength=first + held)[first:first + held]
                   for w in wants)
        expert = int(np.argmax(rows))
        at = {**self.compared, "w_gate": (0, expert)}
        host_bias = after["router_bias"]
        out = compare(self._keye, gots, wants, tol, at, host_bias)
        lap("compare")
        out["sequences"] = sum(int(b["x"].shape[0]) for b in batches)
        out["tokens"] = sum(int(b["x"].size) for b in batches)
        out["compared_layers"] = self.compared
        out["compared_expert"] = first + expert
        out["compared_expert_rows"] = int(rows[expert])
        out["reference_rows_here_by_layer"] = [
            int(sum(np.isin(w["expert_choice"][i],
                            np.arange(first, first + held)).sum()
                    for w in wants)) for i in range(L)]
        out["reference_gate_sum_mean"] = float(
            np.mean([w["gate_sum_mean"] for w in wants]))
        out["polled"] = polled
        # (e); beside it what set-up's passes left: a balance that the
        # window's steps lose shows between the two
        out["window_end"] = end
        out["balance"] = self.balance
        dropped = polled.get("moe.dropped")
        out["moe_dropped"] = dropped
        out["window_change"] = moved
        low, high = moved["leaf_change_least"], moved["leaf_change_most"]
        change_ok = (tol["leaf_change_min"] <= low[1]
                     and high[1] <= tol["leaf_change_max"])
        out["ok"] = bool(out["ok"] and dropped == 0 and change_ok
                         and moved["biases_ok"] and end["held"])

        control = compare(self._keye, [evaluate(b, rounded=True)
                                       for b in routed],
                          wants, tol, at, host_bias)
        lap("control_8bit")
        out["control_8bit"] = {
            "rounded": list(CONTROL_ROUNDS), **caught(control)}
        # the key the harness's rehearsal test reads off every cell's
        # detail line; here it holds the attention's, the MLP's and the
        # experts' matrices in 8 bits
        out["control_lstm_weights_8bit"] = out["control_8bit"]
        # the system as it is against a model WITHOUT what this one adds
        blind = self._reference.layer_tables(
            self.cell.model, as_another_models_block=True)
        control = compare(self._keye, gots,
                          [reference(b, blind) for b in routed], tol, at,
                          host_bias)
        lap("control_other_block")
        out["control_other_block"] = {
            "reference": "no gate on the attention, RoPE on the full layer "
                         "too, a softmax router whose gates sum to one, no "
                         "shared expert",
            **caught(control)}
        out["seconds"] = seconds
        # at the rehearsal's sizes the controls prove nothing about the
        # chip's tolerances: they are reported there, and decide only a
        # chip run
        if not self.cell.rehearse:
            out["ok"] = bool(out["ok"] and out["control_8bit"]["caught"]
                             and out["control_other_block"]["caught"])
        return out


def caught(control: dict) -> dict:
    """A control's readings, which of (b)'s limits it kept (none, where
    the limits tell it from the system), and whether it failed (b)."""
    return {"nll_rms_err": control["nll_rms_err"],
            "grad_fro_err": control["grad_fro_err"],
            "limits_kept": control["limits_kept"],
            "caught": not control["precision_ok"]}


def _fro(gots, wants, name, at) -> float:
    """Frobenius distance of the gradient ``name`` (its part ``at``,
    None: whole), summed over the eval batches, over the reference's
    norm; None where the reference's gradient is zero (the control's
    reference has no gate and no shared expert for one to reach)."""
    import numpy as np

    def part(side):
        g = side["grads"][name]
        return (g if at is None else g[at]).astype(np.float64)

    ref = sum(part(w) for w in wants)
    got = sum(part(g) for g in gots)
    norm = np.linalg.norm(ref)
    return float(np.linalg.norm(got - ref) / norm) if norm else None


def compare(keye, gots: list, wants: list, tol: dict, at: dict,
            bias) -> dict:
    """The system's outputs against the reference's on the eval batches
    (the module's docstring, (a) and (b)); ``at`` says where each
    compared gradient lies: the expert layers of ``wq_sliding`` and
    ``wq_full`` and ``(layer, expert)`` of ``w_gate``; ``bias [L_moe,
    E]`` is what both routers chose under. ``precision_ok`` holds (b),
    which both controls must fail; ``ok`` holds (a) too."""
    import numpy as np

    d = np.concatenate([g["nll"].astype(np.float64) - w["nll"]
                        for g, w in zip(gots, wants)])
    nll_rms = float(np.sqrt(np.mean(d * d)))
    grad_fro = {"wq_sliding": _fro(gots, wants, "wq", at["wq_sliding"]),
                "wq_full": _fro(gots, wants, "wq", at["wq_full"]),
                "w_attn_gate": _fro(gots, wants, "w_attn_gate", None),
                "w_gate": _fro(gots, wants, "w_gate", at["w_gate"]),
                "shared_w_gate": _fro(gots, wants, "shared_w_gate", None),
                "router": _fro(gots, wants, "router", None),
                "dense_w_up": _fro(gots, wants, "dense/w_up", None),
                "emb": _fro(gots, wants, "emb", None)}
    out = {"system_nll": float(np.mean([g["nll"] for g in gots])),
           "reference_nll": float(np.mean([w["nll"] for w in wants])),
           "nll_rms_err": nll_rms,
           "nll_max_err": float(np.max(np.abs(d))),
           "grad_fro_err": grad_fro, **tol}
    kept = [k for k, t in tol["grad_fro_tol"].items()
            if grad_fro[k] is not None and grad_fro[k] <= t]
    if nll_rms <= tol["nll_rms_tol"]:
        kept.append("nll_rms")
    out["limits_kept"] = kept
    out["precision_ok"] = len(kept) == len(tol["grad_fro_tol"]) + 1
    # (a) the experts of a token at the first and the last expert layer
    agree, gap = {}, {}
    last = wants[0]["expert_choice"].shape[0] - 1
    for name, i in (("layer0", 0), ("last", last)):
        parts = []
        for g, w in zip(gots, wants):
            biased = w["router_scores"][i].astype(np.float64) + bias[i]
            n = np.arange(biased.shape[0])[:, None]
            want_e = np.zeros(biased.shape, bool)
            want_e[n, w["expert_choice"][i]] = True
            got_e = np.zeros(biased.shape, bool)
            got_e[n, g["expert_choice"][i]] = True
            eighth = np.where(want_e, biased, np.inf).min(axis=-1,
                                                          keepdims=True)
            parts.append(keye._disputed(got_e, want_e, biased, eighth,
                                        np.ones(biased.shape, bool)))
        counts, gaps = zip(*parts)
        differ, valid = np.sum(counts, axis=0)
        agree[name] = float(1.0 - differ / max(valid, 1))
        gap[name] = float(max(gaps))
    out["expert_agree_share"], out["expert_gap_max"] = agree, gap
    out["ok"] = bool(
        out["precision_ok"]
        and min(agree.values()) >= tol["expert_agree_min"]
        and max(gap.values()) <= tol["expert_gap_tol"])
    return out


def table_change(cfg, moved: dict) -> None:
    """``builders/mellum_train.window_change`` reads the table's change
    over the steps' worth of ``learning_rate``, the rate Mellum2's table
    has; this table has its own (``table_learning_rate``): its reading is
    brought to that rate's worth, and the least and the greatest leaf
    are found again."""
    if cfg.table_learning_rate is None:
        return
    slower = cfg.learning_rate / cfg.table_learning_rate
    leaves = moved["leaf_change"]
    leaves["emb"] *= slower
    moved["table_rate_sum"] /= slower
    by_change = sorted(leaves, key=leaves.get)
    moved["leaf_change_least"] = [by_change[0], leaves[by_change[0]]]
    moved["leaf_change_most"] = [by_change[-1], leaves[by_change[-1]]]


def biases_change(cfg, before: dict, after: dict) -> dict:
    """What the window's steps did to the balancing biases (host copies
    at its two ends; the module's docstring, (c)): a step that dropped
    its new state reads 0, a rule applied twice a step is past the
    bound wherever an expert stays on one side of its layer's mean."""
    import numpy as np

    steps = int(after["step"]) - int(before["step"])
    moved = float(np.max(np.abs(
        after["router_bias"].astype(np.float64) - before["router_bias"])))
    worth = steps * cfg.load_balance_coeff
    return {"bias_moved_max": moved, "bias_moved_steps_worth": worth,
            # float32 sums of ``steps`` equal steps: a part in 1e4 of room
            "biases_ok": bool(0.0 < moved <= worth * 1.0001)}


def build(cell, seed: int) -> System:
    import parallax_tpu as parallax
    from parallax_tpu.models import trinity

    cfg = model_config(cell)
    dep = cell.deployment
    model = trinity.build_model(cfg)
    in_copies = cell.plugin("builders", "keye_train").router_in_copies
    own_init = model.init_fn

    def init_fn(rng):
        # a stateful model's initialiser: the parameters and the biases
        params, state = own_init(rng)
        return in_copies(lambda _: params,
                         int(dep["chips_sharing_a_layer"]),
                         ROUTER_COPY_NOISE)(rng), state

    model.init_fn = init_fn
    sess, *_ = parallax.parallel_run(
        model,
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
