"""Mellum2-12B-A2.5B's trainer, built as a user builds it:
``parallax.parallel_run`` on ``models/mellum2.build_model``, HYBRID plan
with the embedding on the slices path, nothing reached around.

The configuration file's ``model`` block holds ``Mellum2Config``'s
fields under their own names (the chip's share of the deployment:
``experts_held`` experts from ``first_expert`` on, the vocabulary slice,
one period of ``layer_types``); ``deployment`` holds the plan. The
weights are made on the device from ``--seed`` by the engine's own
jitted initialiser; the router starts as Keye's does
(``builders/keye_train.router_in_copies``: one chip's range of 16
columns and a permuted, noised copy for each of the four chips' ranges),
so that the rows routed here stay near the balanced share from seed to
seed.

The comparison that decides ``correct`` (``reference_check``) runs on
the parameters as the window left them, on two sequences of the
generator's eval stream at the timed length, the system's own code
(bfloat16, the flash kernels of BOTH kinds under the scan's ``cond``,
the grouped products' kernel) against the configuration's plain float32
reference:

(a) the experts of a token, the system's own top-8 on the stream the
    reference's routing made: the share of (token, expert) places on
    which the two agree, at the first and at the last layer, and the
    largest distance of a disputed expert's probability from that
    token's eighth: a disagreement is allowed only inside the rounding
    band;
(b) **under ONE routing**, the reference's top-8 of every layer fed to
    both sides (``batch["expert_choice"]``): the negative log-likelihood
    of every position, root mean square of the difference, and the
    gradients of ``wq`` in the first sliding layer AND in the first full
    one, of the fullest held expert's ``w_gate`` (by the reference's
    routing at layer 0) and of the table, Frobenius distance over the
    reference's norm, each held to a limit;
(c) every parameter moved by the steps' worth and no more, LEAF BY LEAF
    (``window_change``): the root mean square of a leaf's change over
    the window, over the sum of the rates its optimizer gave the
    window's steps (the dense group's schedule; the table's constant
    rate, over the rows the window's batches hold: lazy Adam moves no
    other), the WORST leaf on either side held;
(d) ``moe.dropped``, the session's running maximum of the rows routed
    here that no part of ``routed_experts`` covered, is 0;
(e) the balance lasted the window (``builders/zaya_train.window_end``):
    at its last step the fullest held expert is under
    ``load_max_over_mean_max`` of the held mean and the rows routed here
    within ``rows_here_max`` of the held share.

Two negative controls in every chip run, each of which must FAIL (b).
The same comparison with the attention's and the experts' matrices
rounded to 8 bits (``control_8bit``), or the tolerances could not see
matrix products fed a narrower type than the configuration states. And
the system against **the reference with every layer read as full with
the default RoPE** (``control_no_window``: the same compiled reference,
other tables): a program that ignored the window or the YaRN table
would agree with THAT reference, so the limits must tell the two apart.
"""

from __future__ import annotations

import re

# rounded in the 8-bit control: every matrix of the attention and of
# the experts (``layers/<name>``)
CONTROL_ROUNDS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# the independent noise on every router column (Keye's, and why)
ROUTER_COPY_NOISE = 0.15
SLIDING, FULL = "sliding_attention", "full_attention"


def tolerances(cell) -> dict:
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    out = {k: float(tol[k]) for k in (
        "nll_rms_tol", "expert_agree_min", "expert_gap_tol",
        "leaf_change_min", "leaf_change_max", "load_max_over_mean_max",
        "rows_here_max")}
    out["grad_fro_tol"] = {k: float(v)
                           for k, v in tol["grad_fro_tol"].items()}
    return out


def model_config(cell):
    import jax.numpy as jnp
    from parallax_tpu.models import mellum2

    m = dict(cell.model)
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    for key in ("layer_types", "flash_tiles"):
        m[key] = tuple(m[key])
    return mellum2.Mellum2Config(num_partitions=cell.chips, **m)


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
        # the first layer of each kind: whose `wq` gradients are compared
        self.compared = {"wq_sliding": cfg.kinds.index(SLIDING),
                         "wq_full": cfg.kinds.index(FULL)}
        # what Keye's and ZAYA's builders already have
        self._keye = cell.plugin("builders", "keye_train")
        self._zaya = cell.plugin("builders", "zaya_train")

    def static_checks(self) -> list:
        """The embedding on the slices path; on the chip the attention of
        both kinds and the experts' products run by their kernels; the
        compiled step holds no array over tokens x experts held x expert
        width and no float32 array over sequence x sequence. Being the
        harness's last call before the window, it also copies the
        parameters to the host for (c)."""
        import jax

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
        state = self.session.state
        self._before_window = jax.device_get(
            {"params": state.params, "step": state.step})
        return failures

    # -- the system's side of the comparison --------------------------

    def evaluator(self):
        """``evaluate(layers, batch) -> {nll [B, T], grads (the
        reference's ``GRAD_ARRAYS`` and its ``TABLE``), expert_choice [L,
        N, k]}`` by the model's own ``forward`` on the session's
        parameters where the plan placed them, with ``layers`` in place
        of the layer stack; ``batch`` brings the routing
        (``expert_choice``), and the returned choice is what the
        system's router would have chosen at each layer of that
        stream."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import mellum2
        from parallax_tpu.ops import embedding as emb_ops

        engine = self.session.engine
        params = self.session.state.params
        names, table = self._reference.GRAD_ARRAYS, self._reference.TABLE
        cfg = self.cfg

        def loss_of(sub, layers, params, batch):
            layers = {**layers, **{k: sub[k] for k in names}}
            with emb_ops.sharded_lookup_scope(engine.mesh,
                                              engine.plan.sharded_shapes):
                nll, s, choice = mellum2.forward(
                    cfg, {**params, table: sub[table], "layers": layers},
                    batch)
            w = batch["w"]
            loss = jnp.sum(nll * w) / jnp.sum(w) \
                + cfg.router_aux_loss_coef * jnp.mean(s["aux_loss"])
            return loss, (nll, choice)

        @jax.jit
        def run(layers, params, batch):
            sub = {table: params[table], **{k: layers[k] for k in names}}
            (_, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                sub, layers, params, batch)
            return aux, grads

        def evaluate(layers, batch):
            with engine.mesh:
                (nll, choice), grads = run(layers, params, batch)
            return {"nll": np.asarray(nll),
                    "expert_choice": np.asarray(choice),
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
                  if k.startswith("moe.")}
        tol = tolerances(self.cell)
        cfg = self.cfg
        state = self.session.state
        params = state.params
        moved = window_change(
            cfg, self._before_window,
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
        layers = params["layers"]
        L, k = cfg.num_layers, cfg.experts_per_token

        programs = {}

        def reference(batch, tables=None):
            want, grads = self._reference.loss_and_grads(
                params, batch, self.cell.model, tables, programs=programs)
            seconds.setdefault("reference_parts", []).append(
                want.pop("seconds"))
            want = {k: np.asarray(v) for k, v in want.items()}
            want["grads"] = {k: np.asarray(v) for k, v in grads.items()}
            return want

        wants = [reference(b) for b in batches]
        lap("reference")
        # ONE routing: the reference's top-k of every layer, fed to the
        # system (and to the control's reference)
        routed = [{**b, "expert_choice": w["expert_choice"].reshape(
                       L, *b["x"].shape, k).astype(np.int32)}
                  for b, w in zip(batches, wants)]
        evaluate = self.evaluator()
        gots = [evaluate(layers, b) for b in routed]
        lap("system")
        first, held = cfg.first_expert, cfg.experts_held
        # the fullest held expert by the reference's routing at layer 0
        rows = sum(np.bincount(w["expert_choice"][0].ravel(),
                               minlength=first + held)[first:first + held]
                   for w in wants)
        expert = int(np.argmax(rows))
        at = {**self.compared, "w_gate": (0, expert)}
        out = compare(self._keye, gots, wants, tol, at)
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
        out["polled"] = polled
        # the gauge counts the rows of the step's whole batch
        share = self._feeds[0]["x"].size * k * held / cfg.num_experts
        end = self._zaya.window_end(polled, share, tol)
        out["window_end"] = {**end, "held_share_rows": share}
        dropped = polled.get("moe.dropped")
        out["moe_dropped"] = dropped
        out["window_change"] = moved
        low, high = moved["leaf_change_least"], moved["leaf_change_most"]
        change_ok = (tol["leaf_change_min"] <= low[1]
                     and high[1] <= tol["leaf_change_max"])
        out["ok"] = bool(out["ok"] and dropped == 0 and change_ok
                         and end["held"])

        def to_8bit(x):
            # 1 sign, 3 mantissa bits and the exponent's full range (a
            # pair of casts the TPU compiler would remove as excess
            # precision; ``reduce_precision`` it must keep)
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=3)

        rounded = {k: to_8bit(v) if k in CONTROL_ROUNDS else v
                   for k, v in layers.items()}
        control = compare(self._keye, [evaluate(rounded, b) for b in routed],
                          wants, tol, at)
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
        # the system as it is against a model WITHOUT its two kinds
        blind = self._reference.layer_tables(
            self.cell.model, every_layer_full_default_rope=True)
        control = compare(self._keye, gots,
                          [reference(b, blind) for b in routed], tol, at)
        lap("control_no_window")
        out["control_no_window"] = {
            "reference": "every layer full, the default RoPE",
            "nll_rms_err": control["nll_rms_err"],
            "grad_fro_err": control["grad_fro_err"],
            "caught": not control["precision_ok"]}
        out["seconds"] = seconds
        # at the rehearsal's sizes the controls prove nothing about the
        # chip's tolerances: they are reported there, and decide only a
        # chip run
        if not self.cell.rehearse:
            out["ok"] = bool(out["ok"] and out["control_8bit"]["caught"]
                             and out["control_no_window"]["caught"])
        return out


def _fro(gots, wants, name, at) -> float:
    """Frobenius distance of the gradient ``name`` (its part ``at``,
    None: whole), summed over the eval batches, over the reference's
    norm."""
    import numpy as np

    def part(side):
        g = side["grads"][name].astype(np.float64)
        return g if at is None else g[at]

    ref = sum(part(w) for w in wants)
    got = sum(part(g) for g in gots)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def compare(keye, gots: list, wants: list, tol: dict, at: dict) -> dict:
    """The system's outputs against the reference's on the eval batches
    (the module's docstring, (a) and (b)); ``at`` says where each
    compared gradient lies: the layers of ``wq_sliding`` and ``wq_full``
    and ``(layer, expert)`` of ``w_gate``. ``precision_ok`` holds (b),
    which both controls must fail; ``ok`` holds (a) too."""
    import numpy as np

    d = np.concatenate([g["nll"].astype(np.float64) - w["nll"]
                        for g, w in zip(gots, wants)])
    nll_rms = float(np.sqrt(np.mean(d * d)))
    grad_fro = {"wq_sliding": _fro(gots, wants, "wq", at["wq_sliding"]),
                "wq_full": _fro(gots, wants, "wq", at["wq_full"]),
                "w_gate": _fro(gots, wants, "w_gate", at["w_gate"]),
                "emb": _fro(gots, wants, "emb", None)}
    out = {"system_nll": float(np.mean([g["nll"] for g in gots])),
           "reference_nll": float(np.mean([w["nll"] for w in wants])),
           "nll_rms_err": nll_rms,
           "nll_max_err": float(np.max(np.abs(d))),
           "grad_fro_err": grad_fro, **tol}
    out["precision_ok"] = bool(
        nll_rms <= tol["nll_rms_tol"]
        and all(grad_fro[k] <= t for k, t in tol["grad_fro_tol"].items()))
    # (a) the experts of a token at the first and the last layer
    agree, gap = {}, {}
    last = wants[0]["expert_choice"].shape[0] - 1
    for name, i in (("layer0", 0), ("last", last)):
        parts = []
        for g, w in zip(gots, wants):
            probs = w["router_probs"][i].astype(np.float64)
            n = np.arange(probs.shape[0])[:, None]
            want_e = np.zeros(probs.shape, bool)
            want_e[n, w["expert_choice"][i]] = True
            got_e = np.zeros(probs.shape, bool)
            got_e[n, g["expert_choice"][i]] = True
            eighth = np.where(want_e, probs, np.inf).min(axis=-1,
                                                         keepdims=True)
            parts.append(keye._disputed(got_e, want_e, probs, eighth,
                                        np.ones(probs.shape, bool)))
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


def window_change(cfg, before: dict, after: dict, rows_fed) -> dict:
    """What the window's steps did to the parameters (host copies of
    ``params`` and ``step`` at its two ends; the module's docstring,
    (c)). ``rows_fed``: the table's rows the window's batches hold."""
    import jax
    import numpy as np
    from parallax_tpu.models import mellum2

    first, last = int(before["step"]), int(after["step"])
    rate = mellum2.scheduled_rate(cfg)
    rates = [float(rate(t)) if callable(rate) else float(rate)
             for t in range(first, last)]
    table_rates = (last - first) * cfg.learning_rate
    leaves = {}
    for (path, b), a in zip(
            jax.tree_util.tree_leaves_with_path(before["params"]),
            jax.tree_util.tree_leaves(after["params"])):
        name = "/".join(k.key for k in path)
        if name == "emb":
            # lazy Adam at the constant rate, on the rows it was fed
            a, b, worth = a[rows_fed], b[rows_fed], table_rates
        else:
            worth = sum(rates)
        rms = np.sqrt(np.mean(np.square(a - b, dtype=np.float64)))
        leaves[name] = float(rms / worth)
    by_change = sorted(leaves, key=leaves.get)
    return {"steps": last - first, "first_step": first,
            "rate_sum": sum(rates), "table_rate_sum": table_rates,
            "table_rows_fed": int(len(rows_fed)), "leaf_change": leaves,
            "leaf_change_least": [by_change[0], leaves[by_change[0]]],
            "leaf_change_most": [by_change[-1], leaves[by_change[-1]]]}


def build(cell, seed: int) -> System:
    import parallax_tpu as parallax
    from parallax_tpu.models import mellum2

    cfg = model_config(cell)
    dep = cell.deployment
    model = mellum2.build_model(cfg)
    model.init_fn = cell.plugin("builders", "keye_train").router_in_copies(
        model.init_fn, int(dep["chips_sharing_a_layer"]), ROUTER_COPY_NOISE)
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
