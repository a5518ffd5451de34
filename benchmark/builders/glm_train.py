"""GLM-4.7-Flash's trainer, built as a user builds it:
``parallax.parallel_run`` on ``models/glm4_moe_lite.build_model`` (a
stateful ``Model``: the routers' balancing biases, the MTP block's last,
are its ``model_state``), HYBRID plan with the embedding on the slices
path, nothing reached around.

The configuration file's ``model`` block holds ``GlmConfig``'s fields
under their own names (the chip's share of the deployment:
``experts_held`` experts from ``first_expert`` on, the vocabulary slice,
the leading dense layer, four expert layers and the MTP block);
``deployment`` holds the plan. The weights are made on the device from
``--seed`` by the engine's own jitted initialiser; every router, the MTP
block's too, starts as Keye's does (``builders/keye_train.router_in_copies``:
one chip's range of 8 columns and a permuted, noised copy for each of the
eight chips' ranges), so that the rows routed here stay near the
balanced share from seed to seed.

The comparison that decides ``correct`` (``reference_check``) runs on
the parameters and the biases as the window left them, on two sequences
of the generator's eval stream at the timed length, the system's own
code (bfloat16, the flash kernels at heads of 256, the grouped products'
kernel) against the configuration's plain float32 reference:

(a) the experts of a token, the system's own top-4 of ``s + b`` on the
    stream the reference's routing made: the share of (token, expert)
    places on which the two agree, at the first and the last expert
    layer and the MTP block's, and the largest distance of a disputed
    expert's biased score from that token's fourth: a disagreement is
    allowed only inside the rounding band;
(b) **under ONE routing**, the reference's top-4 of every expert layer
    (the MTP block's among them) fed to both sides
    (``batch["expert_choice"]``): the negative log-likelihood of every
    position of the main stream and of every weighed position of the
    MTP stream, root mean square of the difference each, and the
    gradients of ``wq_a`` and ``wkv_b`` (every expert layer's), of the
    fullest held expert's ``w_gate`` (by the reference's routing at the
    first expert layer), of the shared expert's gate matrix and of the
    router (every expert layer's), of the dense layer's ``w_up``, of the
    MTP block's ``w_eh`` and of the table, Frobenius distance over the
    reference's norm, each held to a limit;
(c) every parameter moved by the steps' worth and no more, LEAF BY LEAF
    (``builders/mellum_train.window_change``, the table at its own rate:
    ``builders/trinity_train.table_change``), the WORST leaf on either
    side held; and the biases, the state no gradient reaches: they
    moved, by no more than the window's steps times
    ``load_balance_coeff`` (``builders/trinity_train.biases_change``);
(d) ``moe.dropped``, the session's running maximum of the rows routed
    here that no part of ``routed_experts`` covered, is 0;
(e) the table's update in the timed step (``System.table_step``): ONE
    more step of the session's own compiled program on the first eval
    batch, the touched rows of the table and their Adam moments read
    before and after it, against the reference's lazy Adam
    (``reference.lazy_adam_rows``) from the same rows and moments with
    the reference's gradient of the table, both lookups' rows summed:
    the rows' move and the first moment's, each a Frobenius distance
    over the reference step's own, held to a limit, and the step counted
    once. A step that dropped the MTP lookup's rows, or applied the
    update twice, is past its limit (``PERF.md`` section 6 has both
    faults' readings on the chip); (c) cannot tell them, because Adam
    normalises a step whatever the gradient's size.

A negative control in every chip run, which must FAIL (b): the same
comparison with every matrix of the attention, of the dense MLP, of the
experts and of the MTP block's input rounded to 8 bits INSIDE the
evaluator's program (no second copy of them lives beside the session's
state), or the limits could not see matrix products fed a narrower type
than the configuration states.
"""

from __future__ import annotations

import re

# rounded in the 8-bit control: every matrix of the attention, of the
# dense MLP, of the experts and of the MTP block's input
# (``dense/<name>``, ``layers/<name>``, ``mtp/<name>``)
CONTROL_ROUNDS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
                  "w_down", "shared_w_gate", "shared_w_up", "shared_w_down",
                  "w_eh")
# the independent noise on every router column (Keye's, and why)
ROUTER_COPY_NOISE = 0.15
# the expert layers whose choices (a) holds: the first, the last of the
# loop and the MTP block's
AGREEMENT_AT = ("layer0", "last", "mtp")
# set-up's routing passes, the same for every seed: thirty rounds of the
# cell's four batches. Without them the rows routed here at the
# window's end read 3,661-5,001 a layer by the seed (4,096 balanced),
# and the rate followed them: 23,039.8 against 22,922.8 tokens/s/chip
# for 3,989 against 5,001 rows on a TPU v5e (PERF.md section 6)
BALANCE_PASSES = 120


def tolerances(cell) -> dict:
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    out = {k: float(tol[k]) for k in (
        "nll_rms_tol", "mtp_nll_rms_tol", "expert_agree_min",
        "expert_gap_tol", "leaf_change_min", "leaf_change_max",
        "table_move_tol", "table_moment_tol")}
    out["grad_fro_tol"] = {k: float(v)
                           for k, v in tol["grad_fro_tol"].items()}
    return out


def model_config(cell):
    import jax.numpy as jnp
    from parallax_tpu.models import glm4_moe_lite as glm

    m = dict(cell.model)
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    m["flash_tiles"] = tuple(m["flash_tiles"])
    # what the roofline readers take off the block and the model derives:
    # as many key/value heads as query heads, one head size
    heads, size = m.pop("num_kv_heads"), m.pop("head_dim")
    cfg = glm.GlmConfig(num_partitions=cell.chips, **m)
    if (heads, size) != (cfg.num_heads, cfg.head_dim):
        raise ValueError(f"model.num_kv_heads {heads} and head_dim {size} "
                         f"are not the model's {cfg.num_heads} and "
                         f"{cfg.head_dim}")
    return cfg


class System:
    def __init__(self, cell, session, cfg, reference, feeds):
        self.cell = cell
        self.session = session
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self._reference = reference
        self._feeds = feeds
        # what the window starts from: the parameters, the biases (host
        # copies) and the step counter; what set-up's routing passes saw
        self._before_window = None
        self.balance = None
        self._keye = cell.plugin("builders", "keye_train")
        self._mellum = cell.plugin("builders", "mellum_train")
        self._trinity = cell.plugin("builders", "trinity_train")

    def _state(self) -> dict:
        import jax
        state = self.session.state
        return jax.device_get(
            {"params": state.params, "step": state.step,
             "router_bias": state.model_state["router_bias"]})

    def _gauges(self) -> dict:
        """The last step's outputs as the session polled them."""
        return {k: v for k, v in self.session.metrics_snapshot().items()
                if k.startswith(("moe.", "router.", "mtp."))}

    def bring_biases_to_rest(self) -> dict:
        """``BALANCE_PASSES`` routing passes over the cell's batches by
        the model's own forward and its own rule (``ops/moe.balance_step``
        at ``load_balance_coeff`` a pass), no weight moving; the biases
        then go into the session. Returns, for the first and the last
        round of the batches and every fifth round between, the rows
        routed here a layer and the fullest held expert over the held
        experts' mean (the layers' means over the round's batches)."""
        import jax
        import numpy as np
        from parallax_tpu.models import glm4_moe_lite as glm
        from parallax_tpu.ops import embedding as emb_ops
        from parallax_tpu.ops import moe as moe_ops

        cfg, engine = self.cfg, self.session.engine
        first, held = cfg.first_expert, cfg.experts_held

        @jax.jit
        def route(params, bias, batch):
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                _, _, s, _ = glm.forward(cfg, params, bias, batch)
            return moe_ops.balance_step(
                bias, s["load"], cfg.load_balance_coeff), s["load"]

        def reading(loads):
            mine = np.stack([np.asarray(load)[:, first:first + held]
                             for load in loads])       # [batches, L, held]
            rows = mine.sum(axis=-1)
            return {"rows_here": float(np.mean(rows)),
                    "load_max_over_mean": float(np.mean(
                        mine.max(axis=-1) * held / np.maximum(rows, 1.0)))}

        params = self.session.state.params
        bias = self.session.state.model_state["router_bias"]
        rounds = []
        with engine.mesh:
            for _ in range(BALANCE_PASSES // len(self._feeds)):
                loads = []
                for feed in self._feeds:
                    bias, load = route(params, bias, feed)
                    loads.append(load)
                rounds.append(reading(loads))
        self.session.set_model_state({"router_bias": bias})
        share = self._feeds[0]["x"].size * cfg.experts_per_token * held \
            / cfg.num_experts
        return {"passes": len(rounds) * len(self._feeds),
                "held_share_rows": share, **rounds[-1],
                "first_round": rounds[0],
                "every_fifth_round": [
                    [i, round(r["rows_here"]), round(r["load_max_over_mean"],
                                                      3)]
                    for i, r in enumerate(rounds) if i % 5 == 0]}

    def static_checks(self) -> list:
        """The embedding on the slices path; on the chip the attention
        and the experts' products run by their kernels; the compiled step
        holds no array over tokens x experts held x expert width and no
        float32 array over sequence x sequence. Being the harness's last
        call before the window, it ends with set-up's routing passes
        (``bring_biases_to_rest``: the one change of state set-up makes
        after the warm steps) and copies the state the window starts
        from."""
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
        self._before_window = self._state()
        return failures

    # -- the system's side of the comparison --------------------------

    def evaluator(self):
        """``evaluate(batch, rounded) -> {nll [B, T], mtp_nll [B, T],
        grads (the reference's ``compared`` leaves), expert_choice [L_moe
        + 1, N, k]}`` by the model's own ``forward`` and loss on the
        session's parameters and biases where the plan placed them;
        ``batch`` brings the routing (``expert_choice``), and the
        returned choice is what the system's router would have chosen at
        each expert layer of that stream. With ``rounded`` every matrix
        of ``CONTROL_ROUNDS`` is rounded to 8 bits on its way into the
        cast the forward makes anyway: one program serves the comparison
        and its control."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import glm4_moe_lite as glm
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
            for stack in ("dense", "layers", "mtp"):
                params[stack] = {
                    k: to_8bit(v, rounded) if k in CONTROL_ROUNDS else v
                    for k, v in params[stack].items()}
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                nll, mtp_nll, _, choice = glm.forward(cfg, params, bias,
                                                      batch)
            loss, _, _ = glm.total_loss(cfg, batch, nll, mtp_nll)
            return loss, (nll, mtp_nll, choice)

        @jax.jit
        def run(params, bias, batch, rounded):
            (_, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                ref.compared(params), params, bias, batch, rounded)
            return aux, grads

        def evaluate(batch, rounded=False):
            with engine.mesh:
                (nll, mtp_nll, choice), grads = run(
                    params, bias, batch, jnp.asarray(bool(rounded)))
            return {"nll": np.asarray(nll), "mtp_nll": np.asarray(mtp_nll),
                    "expert_choice": np.asarray(choice),
                    "grads": {k: np.asarray(v) for k, v in grads.items()}}

        return evaluate

    def table_step(self, batch, grad, tol: dict) -> dict:
        """(e): one step of the session's own program on ``batch``, which
        has the window's shapes (no new program), against the reference's
        lazy Adam with ``grad``, the reference's gradient of the whole
        table at the same parameters. Last of the check: it moves the
        state."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        sess, cfg, ref = self.session, self.cfg, self._reference
        rows = np.unique(np.concatenate([batch["x"].ravel(),
                                         batch["y"].ravel()]))
        at = jnp.asarray(rows)

        def touched():
            state = sess.state
            adam = state.slice_state["emb"]
            return jax.device_get({
                "rows": jnp.take(state.params["emb"], at, axis=0),
                "m": jnp.take(adam.m, at, axis=0),
                "v": jnp.take(adam.v, at, axis=0), "count": adam.count})

        before = touched()
        sess.run("loss", feed_dict=batch)
        after = touched()
        rate = cfg.learning_rate if cfg.table_learning_rate is None \
            else cfg.table_learning_rate
        want, m, _ = ref.lazy_adam_rows(before["rows"], before["m"],
                                        before["v"], before["count"],
                                        grad[rows], rate)

        def distance(got, want, start):
            # over the reference step's own size: the rows' move, the
            # first moment's (1 - b1) g
            return float(np.linalg.norm(got.astype(np.float64) - want)
                         / np.linalg.norm(want - start))

        move = distance(after["rows"], want, before["rows"])
        moment = distance(after["m"], m, ref.ADAM_B1 * before["m"])
        steps = int(after["count"]) - int(before["count"])
        return {"rows": int(rows.size), "steps": steps,
                "table_move_err": move, "table_moment_err": moment,
                "ok": bool(steps == 1 and move <= tol["table_move_tol"]
                           and moment <= tol["table_moment_tol"])}

    def reference_check(self, seed: int) -> dict:
        import time

        import numpy as np

        clock = [time.perf_counter()]
        seconds = {}

        def lap(name):
            clock.append(time.perf_counter())
            seconds[name] = round(clock[-1] - clock[-2], 2)

        polled = self._gauges()
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
        self._trinity.table_change(cfg, moved)
        moved.update(self._trinity.biases_change(cfg, before, after))
        lap("window_change")

        generator = self.cell.plugin("generators",
                                     self.cell.traffic["generator"])
        # two batches of a step's rows: (e) steps on the first
        n = int(self.cell.mix["global_batch"])
        both = generator.make_eval(self.cell.mix, seed, self.vocab_size,
                                   2 * n)
        batches = [{k: v[i * n:(i + 1) * n] for k, v in both.items()}
                   for i in range(2)]
        L, k = cfg.num_moe_layers + cfg.num_mtp_layers, cfg.experts_per_token

        programs = {}

        def reference(batch):
            want, grads = self._reference.loss_and_grads(
                params, bias, batch, self.cell.model, programs=programs)
            seconds.setdefault("reference_parts", []).append(
                want.pop("seconds"))
            want = {k: np.asarray(v) for k, v in want.items()}
            want["grads"] = {k: np.asarray(v) for k, v in grads.items()}
            return want

        wants = [reference(b) for b in batches]
        lap("reference")
        # ONE routing: the reference's top-k of every expert layer, fed
        # to the system
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
        host_bias = after["router_bias"]
        out = compare(self._keye, self._trinity, gots, wants, tol, expert,
                      host_bias)
        lap("compare")
        out["sequences"] = sum(int(b["x"].shape[0]) for b in batches)
        out["tokens"] = sum(int(b["x"].size) for b in batches)
        out["compared_expert"] = first + expert
        out["compared_expert_rows"] = int(rows[expert])
        out["reference_rows_here_by_layer"] = [
            int(sum(np.isin(w["expert_choice"][i],
                            np.arange(first, first + held)).sum()
                    for w in wants)) for i in range(L)]
        out["reference_gate_sum_mean"] = float(
            np.mean([w["gate_sum_mean"] for w in wants]))
        out["polled"] = polled
        out["balance"] = self.balance
        dropped = polled.get("moe.dropped")
        out["moe_dropped"] = dropped
        out["window_change"] = moved
        low, high = moved["leaf_change_least"], moved["leaf_change_most"]
        change_ok = (tol["leaf_change_min"] <= low[1]
                     and high[1] <= tol["leaf_change_max"])
        out["ok"] = bool(out["ok"] and dropped == 0 and change_ok
                         and moved["biases_ok"])

        control = compare(self._keye, self._trinity,
                          [evaluate(b, rounded=True) for b in routed],
                          wants, tol, expert, host_bias)
        lap("control_8bit")
        out["control_8bit"] = {
            "rounded": list(CONTROL_ROUNDS),
            **self._trinity.caught(control),
            "mtp_nll_rms_err": control["mtp_nll_rms_err"]}
        # the key the harness's rehearsal test reads off every cell's
        # detail line; here it holds the attention's, the MLP's, the
        # experts' and the MTP block's matrices in 8 bits
        out["control_lstm_weights_8bit"] = out["control_8bit"]
        out["table_step"] = self.table_step(
            batches[0], wants[0]["grads"]["emb"], tol)
        lap("table_step")
        out["ok"] = bool(out["ok"] and out["table_step"]["ok"])
        out["seconds"] = seconds
        # at the rehearsal's sizes the control proves nothing about the
        # chip's limits: it is reported there, and decides only a chip
        # run
        if not self.cell.rehearse:
            out["ok"] = bool(out["ok"] and out["control_8bit"]["caught"])
        return out


def compare(keye, trinity, gots: list, wants: list, tol: dict, expert: int,
            bias) -> dict:
    """The system's outputs against the reference's on the eval batches
    (the module's docstring, (a) and (b)); ``expert`` is the held expert
    whose ``w_gate`` gradient at the first expert layer is compared;
    ``bias [L_moe + 1, E]`` is what both routers chose under.
    ``precision_ok`` holds (b), which the control must fail; ``ok``
    holds (a) too."""
    import numpy as np

    def rms(key, weighed):
        d = np.concatenate([(g[key].astype(np.float64) - w[key])[:, weighed]
                            for g, w in zip(gots, wants)])
        return float(np.sqrt(np.mean(d * d))), float(np.max(np.abs(d)))

    T = wants[0]["nll"].shape[1]
    nll_rms, nll_max = rms("nll", slice(None))
    # the MTP stream's last position has no label
    mtp_rms, mtp_max = rms("mtp_nll", slice(0, T - 1))

    # the gradient's name in the reference's ``compared`` and its part
    at = {"wq_a": ("wq_a", None), "wkv_b": ("wkv_b", None),
          "w_gate": ("w_gate", (0, expert)),
          "shared_w_gate": ("shared_w_gate", None),
          "router": ("router", None), "dense_w_up": ("dense/w_up", None),
          "mtp_w_eh": ("mtp/w_eh", None), "emb": ("emb", None)}
    grad_fro = {k: trinity._fro(gots, wants, name, part)
                for k, (name, part) in at.items()}
    out = {"system_nll": float(np.mean([g["nll"] for g in gots])),
           "reference_nll": float(np.mean([w["nll"] for w in wants])),
           "system_mtp_nll": float(np.mean([g["mtp_nll"][:, :T - 1]
                                            for g in gots])),
           "reference_mtp_nll": float(np.mean([w["mtp_nll"][:, :T - 1]
                                               for w in wants])),
           "nll_rms_err": nll_rms, "nll_max_err": nll_max,
           "mtp_nll_rms_err": mtp_rms, "mtp_nll_max_err": mtp_max,
           "grad_fro_err": grad_fro, **tol}
    kept = [k for k, t in tol["grad_fro_tol"].items()
            if grad_fro[k] is not None and grad_fro[k] <= t]
    if nll_rms <= tol["nll_rms_tol"]:
        kept.append("nll_rms")
    if mtp_rms <= tol["mtp_nll_rms_tol"]:
        kept.append("mtp_nll_rms")
    out["limits_kept"] = kept
    out["precision_ok"] = len(kept) == len(tol["grad_fro_tol"]) + 2
    # (a) the experts of a token at the first and the last expert layer
    # of the loop and at the MTP block's
    agree, gap = {}, {}
    layers = wants[0]["expert_choice"].shape[0]
    for name, i in zip(AGREEMENT_AT, (0, layers - 2, layers - 1)):
        parts = []
        for g, w in zip(gots, wants):
            biased = w["router_scores"][i].astype(np.float64) + bias[i]
            n = np.arange(biased.shape[0])[:, None]
            want_e = np.zeros(biased.shape, bool)
            want_e[n, w["expert_choice"][i]] = True
            got_e = np.zeros(biased.shape, bool)
            got_e[n, g["expert_choice"][i]] = True
            last = np.where(want_e, biased, np.inf).min(axis=-1,
                                                        keepdims=True)
            parts.append(keye._disputed(got_e, want_e, biased, last,
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


def build(cell, seed: int) -> System:
    import jax
    import parallax_tpu as parallax
    from parallax_tpu.models import glm4_moe_lite as glm

    cfg = model_config(cell)
    dep = cell.deployment
    model = glm.build_model(cfg)
    in_copies = cell.plugin("builders", "keye_train").router_in_copies
    copies = int(dep["chips_sharing_a_layer"])
    own_init = model.init_fn

    def init_fn(rng):
        # a stateful model's initialiser: the parameters and the biases;
        # the loop's routers and the MTP block's each in copies
        params, state = own_init(rng)
        params = in_copies(lambda _: params, copies, ROUTER_COPY_NOISE)(rng)
        mtp = params["mtp"]
        copied = in_copies(
            lambda _: {"layers": {"router": mtp["router"][None]}}, copies,
            ROUTER_COPY_NOISE)(jax.random.fold_in(rng, 1))
        router = copied["layers"]["router"][0]
        return {**params, "mtp": {**mtp, "router": router}}, state

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
