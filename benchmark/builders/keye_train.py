"""The Keye-VL-2.0 language model's trainer, built as a user builds it:
``parallax.parallel_run`` on ``models/keye_vl2.build_model``, HYBRID
plan, nothing reached around.

The configuration file's ``model`` block holds ``KeyeVL2Config``'s
fields under their own names (the chip's share of the deployment:
``experts_held`` experts from ``first_expert`` on, the vocabulary
slice); ``deployment`` holds the plan. The weights are made on the
device from ``--seed`` by the engine's own jitted initialiser.

**How the router starts** is this builder's, not the model's
(``router_in_copies``): one chip's range of columns from the model's
own initialiser, and for each further chip of the expert-parallel group
a permuted copy of it, every column with independent noise of its own
added (``ROUTER_COPY_NOISE`` of a column's scale). All 128
columns differ and the top-8 runs over 128 distinct logits with unequal
gates; but the ranges are correlated, so that a token's choices spread
over the chips' ranges and the rows routed to this chip stay near the
balanced share from seed to seed (PERF.md section 6, PR 27, has why
128 independent columns cannot: a quarter of a Zipf(1.3) sequence is
one id, and equal ids are routed alike).

The comparison that decides ``correct`` (``reference_check``) runs on
the trained parameters, on two sequences of the generator's eval stream
at the timed length, the system's own code (bfloat16, the grouped
products' kernel, the threshold selection) against the configuration's
plain float32 reference:

(a) the negative log-likelihood of every position, root mean square of
    the difference;
(b) the gradient of the three-part loss in layer 0's ``wq``: Frobenius
    distance over the reference's norm. The same distance for one held
    expert's ``w_gate`` (layer 0, the fullest expert by the reference's
    routing) and for layer 0's ``idx_wq`` (which only the indexer's
    loss reaches) is REPORTED and holds no limit: a token that the
    system's bfloat16 activations route (or select) otherwise than the
    reference's, inside the rounding band of (c), moves its whole row
    from one expert's gradient to another's, so those two read as far
    as the 8-bit control's least reading (``w_gate_by_expert`` lists
    every held expert's rows, disputed rows and distance);
(c) at layer 0, the share of causal (query, key) pairs on which the
    system's selection and the reference's agree, and the largest gap
    between a disputed key's reference score and that row's last
    selected score, in units of the scores' root mean square: a
    disputed key must lie inside the rounding band of the threshold;
    the same for the experts of a token, in probability;
(d) ``moe.dropped``, the session's running maximum of the rows routed
    here that no part of ``routed_experts`` covered, is 0;
(e) the same model under a router of 128 independent columns, those of
    the held range three times as long: eight independent choices a
    token, and two to four times the balanced share of rows, so that
    the second part of ``routed_experts`` (the rows past
    ``moe.fast_rows``, which no timed step reaches) runs and is
    compared: NLL, ``wq``'s gradient, the experts' agreement as in (c),
    and the rows at layer 0, which must pass ``fast_rows``;
(f) the norm of the dense parameters' change over the window (from
    the two warm steps' end, which the schedule's rates 0 and 3e-7
    leave at the initialiser's), over their norm before it, lies
    between its two limits: the optimizer moved them, and by the
    scheduled rate.

Two negative controls in every chip run. The same comparison with the
attention's and the experts' matrices rounded to 8 bits must FAIL (a)
or (b), or the tolerances could not see matrix products fed a narrower
type than the configuration states. And the same model built with
``indexer_topk = seq_len``, which is dense causal attention by the
model's own equations, must FAIL (a): a comparison that dense attention
passes does not see the selection.
"""

from __future__ import annotations

import dataclasses
import re

# rounded in the 8-bit control: every matrix of the attention and of
# the experts (``layers/<name>``)
CONTROL_ROUNDS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# the layer whose gradients, selection and routing are compared
COMPARE_LAYER = 0


# the held range's columns are this many times as long under (e)
SKEW = 3.0
# the independent noise on every router column, in units of a column's
# scale (``router_in_copies``): the largest at which the rows routed to
# one chip stay within a tenth of the balanced share from seed to seed
ROUTER_COPY_NOISE = 0.15


def tolerances(cell) -> dict:
    tol = dict(cell.config["tolerances"])
    if cell.rehearse:
        tol.update(cell.config.get("rehearse_tolerances", {}))
    out = {k: float(tol[k]) for k in (
        "nll_rms_tol", "selection_agree_min", "selection_gap_tol",
        "expert_agree_min", "expert_gap_tol", "skew_nll_rms_tol",
        "skew_expert_gap_tol", "param_change_min", "param_change_max")}
    out["grad_fro_tol"] = {k: float(v)
                           for k, v in tol["grad_fro_tol"].items()}
    return out


def router_in_copies(init_fn, copies: int, noise: float):
    """``init_fn`` with the router's start replaced: the first ``E /
    copies`` columns as ``init_fn`` draws them, and for each of the
    ``copies`` ranges a permutation of those columns plus independent
    noise of ``noise`` times a column's scale on every column, the sum
    brought back to that scale. No two columns are equal; the ranges
    are correlated (``1 / (1 + noise^2)``), so a token's top choices
    spread over the ranges."""
    def init(rng):
        import jax
        import jax.numpy as jnp

        params = init_fn(rng)
        router = params["layers"]["router"]
        L, D, E = router.shape
        per = E // copies
        k_perm, k_noise = jax.random.split(jax.random.fold_in(rng, 27))
        perms = jax.vmap(lambda k: jax.random.permutation(k, per))(
            jax.random.split(k_perm, L * copies)).reshape(L, 1, E)
        columns = jnp.take_along_axis(router[..., :per], perms, axis=2)
        own = jax.random.normal(k_noise, router.shape, router.dtype) \
            * (noise / jnp.sqrt(D))
        layers = {**params["layers"],
                  "router": (columns + own) / jnp.sqrt(1.0 + noise * noise)}
        return {**params, "layers": layers}
    return init


def model_config(cell, **overrides):
    import jax.numpy as jnp
    from parallax_tpu.models import keye_vl2

    m = dict(cell.model)
    m["compute_dtype"] = jnp.dtype(m["compute_dtype"])
    m["mrope_section"] = tuple(m["mrope_section"])
    m.update(overrides)
    return keye_vl2.KeyeVL2Config(num_partitions=cell.chips, **m)


class System:
    def __init__(self, cell, session, cfg, reference):
        self.cell = cell
        self.session = session
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self._reference = reference
        self._before_window = None

    def static_checks(self) -> list:
        """The embedding on the slices path; on the chip the experts'
        products run by the grouped kernel; the compiled step holds no
        array over tokens x experts held x expert width (every expert
        for every token) and no float32 array over sequence x sequence
        (the scores held whole). Being the harness's last call before
        the window, it also copies the parameters to the host for (f):
        the session makes its state with the first feed, and the warm
        steps before this run at the schedule's first two rates, 0 and
        3e-7."""
        import jax

        self._before_window = jax.device_get(self.session.state.params)
        failures = []
        engine = self.session.engine
        tables = sorted(self.session.state.slice_state or ())
        if tables != ["emb"]:
            failures.append(f"the embedding is not on the slices path "
                            f"(slice tables: {tables})")
        index = self.session.layer_index()
        if index is None:
            return failures + ["no compiled step to read"]
        kernels = [n for n, m in index["hlo_index"].items()
                   if m["opcode"] == "custom-call"
                   and index["layers"][n] == "moe"]
        if not self.cell.rehearse and not kernels:
            failures.append("no custom call under the scope `moe`: the "
                            "experts' products are not the grouped kernel's")
        if self.cell.rehearse:
            # off the chip the grouped products are XLA's ragged dot,
            # which the CPU lowers to every expert for every row
            return failures
        text = engine.executable_text()
        T = int(self.cell.mix["num_steps"]) \
            * int(self.cell.mix["global_batch"]) // self.cell.chips
        dense_experts = re.compile(
            rf"\[{T},{self.cfg.experts_held},{self.cfg.expert_dim}\]")
        whole_scores = re.compile(rf"f32\[(1,)?{T},{T}\]")
        for what, pat in (("tokens x experts held", dense_experts),
                          ("whole float32 scores", whole_scores)):
            if pat.search(text):
                failures.append(f"the compiled step holds an array over "
                                f"{what}: {pat.pattern}")
        return failures

    # -- the system's side of the comparison --------------------------

    def evaluator(self):
        """``evaluate(layers, batch, topk) -> {nll [B, T], grads,
        selection, expert_choice}`` by the model's own ``call_loss`` and
        ``layer_selection``, on the session's parameters where the plan
        placed them, with ``layers`` in place of the layer stack and
        ``topk`` in place of the configuration's ``indexer_topk`` (a
        traced scalar: the dense-attention control runs the same
        compiled program). ``call_loss`` returns ``sum(l * w) / sum(w)``
        plus terms that do not read ``w``, so ``l = lm_loss + d loss / d
        w * sum(w)`` comes out of the same call as the gradients."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from parallax_tpu.models import keye_vl2
        from parallax_tpu.ops import embedding as emb_ops

        engine = self.session.engine
        params = self.session.state.params
        names = self._reference.GRAD_ARRAYS

        def scope():
            return emb_ops.sharded_lookup_scope(
                engine.mesh, engine.plan.sharded_shapes)

        def loss_of(sub, w, layers, params, batch, cfg):
            with scope():
                loss, metrics, _ = keye_vl2.build_model(cfg).call_loss(
                    {**params, "layers": {**layers, **sub}},
                    {**batch, "w": w}, jax.random.PRNGKey(0))
            return loss, metrics["lm_loss"]

        @jax.jit
        def run(layers, params, batch, topk):
            cfg = dataclasses.replace(self.cfg, indexer_topk=topk)
            sub = {k: layers[k] for k in names}
            (_, lm_loss), (g_sub, g_w) = jax.value_and_grad(
                loss_of, argnums=(0, 1), has_aux=True)(
                    sub, batch["w"], layers, params, batch, cfg)
            return lm_loss + g_w * jnp.sum(batch["w"]), g_sub

        @jax.jit
        def selection(layers, params, batch, topk):
            cfg = dataclasses.replace(self.cfg, indexer_topk=topk)
            with scope():
                return keye_vl2.layer_selection(
                    cfg, {**params, "layers": layers}, batch,
                    COMPARE_LAYER)

        def evaluate(layers, batch, topk, with_selection=True):
            topk = np.int32(topk)
            with engine.mesh:
                nll, grads = run(layers, params, batch, topk)
                picked = selection(layers, params, batch, topk) \
                    if with_selection else {}
            return {"nll": np.asarray(nll),
                    "grads": {k: np.asarray(v) for k, v in grads.items()},
                    **{k: np.asarray(v) for k, v in picked.items()}}

        return evaluate

    def reference_check(self, seed: int) -> dict:
        import time

        import jax
        import numpy as np
        from parallax_tpu.ops import moe as moe_ops

        clock = [time.perf_counter()]
        seconds = {}

        def lap(name):
            clock.append(time.perf_counter())
            seconds[name] = round(clock[-1] - clock[-2], 2)

        # the last step's outputs as the session polled them
        polled = {k: v for k, v in self.session.metrics_snapshot().items()
                  if k.startswith(("moe.", "sparse_attn."))}
        tol = tolerances(self.cell)
        params = self.session.state.params
        change = param_change(self._before_window, jax.device_get(params))
        self._before_window = None
        lap("param_change")

        generator = self.cell.plugin("generators",
                                     self.cell.traffic["generator"])
        chips = self.cell.chips
        both = generator.make_eval(self.cell.mix, seed, self.vocab_size,
                                   2 * chips)
        batches = [{k: v[i * chips:(i + 1) * chips] for k, v in both.items()}
                   for i in range(2)]
        layers = params["layers"]

        programs = {}

        def reference(layers, batch):
            want, grads = self._reference.loss_and_grads(
                {**params, "layers": layers}, batch, self.cell.model,
                collect_layer=COMPARE_LAYER, programs=programs)
            seconds.setdefault("reference_parts", []).append(
                want.pop("seconds"))
            want = {k: np.asarray(v) for k, v in want.items()}
            want["grads"] = {k: np.asarray(v) for k, v in grads.items()}
            return want

        wants = [reference(layers, b) for b in batches]
        lap("reference")
        evaluate = self.evaluator()
        topk = self.cfg.indexer_topk
        gots = [evaluate(layers, b, topk) for b in batches]
        lap("system")
        first, held = self.cfg.first_expert, self.cfg.experts_held
        experts = held_experts(
            np.concatenate([g["expert_choice"] for g in gots]),
            np.concatenate([w["expert_choice"] for w in wants]), first, held)
        # the fullest held expert by the reference's routing: the most rows,
        # so the least share of them disputed
        expert = max(range(held), key=lambda e: experts[e][0])
        out = compare(gots, wants, tol, expert)
        lap("compare")
        out["sequences"] = sum(int(b["x"].shape[0]) for b in batches)
        out["tokens"] = sum(int(b["x"].size) for b in batches)
        out["compared_expert"] = first + expert
        # [rows by the reference, rows the two sides dispute, the
        # gradient's distance] of every held expert, for the record
        out["w_gate_by_expert"] = [
            [*experts[e], round(_fro(gots, wants, "w_gate", e), 5)
             if experts[e][0] else None]
            for e in range(held)]
        out["routing"] = routing(wants[0], first, held)
        out["polled"] = polled
        dropped = polled.get("moe.dropped")
        out["moe_dropped"] = dropped
        out["param_change"] = change
        change_ok = (tol["param_change_min"] <= change["dense"]
                     <= tol["param_change_max"])
        out["ok"] = bool(out["ok"] and dropped == 0 and change_ok)

        def to_8bit(x):
            # 1 sign, 3 mantissa bits and the exponent's full range (a
            # pair of casts the TPU compiler would remove as excess
            # precision; ``reduce_precision`` it must keep)
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=3)

        def rounded(layers):
            return {k: to_8bit(v) if k in CONTROL_ROUNDS else v
                    for k, v in layers.items()}

        def again(layers, topk, batches=batches, wants=wants):
            return compare([evaluate(layers, b, topk, with_selection=False)
                            for b in batches], wants, tol, expert)

        control = again(rounded(layers), topk)
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
        control_dense = again(layers, self.cfg.seq_len)
        lap("control_dense")
        out["control_dense_attention"] = {
            "indexer_topk": self.cfg.seq_len,
            "nll_rms_err": control_dense["nll_rms_err"],
            "caught": control_dense["nll_rms_err"] > tol["nll_rms_tol"]}

        # (e) 128 independent columns, the held range's SKEW times as
        # long, on the first sequence
        key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % 2**31), 5)
        router = jax.random.normal(key, layers["router"].shape) \
            / np.sqrt(self.cfg.model_dim)
        router = router.at[..., first:first + held].multiply(SKEW)
        skewed = {**layers, "router": router}
        one = batches[:1]
        want_s = [reference(skewed, one[0])]
        got_s = [evaluate(skewed, one[0], topk)]
        for side in (want_s[0], got_s[0]):
            side.pop("selection")       # layer 0's selection is (c)'s
        skew = compare(got_s, want_s,
                       {**tol, "nll_rms_tol": tol["skew_nll_rms_tol"],
                        "expert_gap_tol": tol["skew_expert_gap_tol"]},
                       expert)
        rows = int(np.isin(got_s[0]["expert_choice"],
                           np.arange(first, first + held)).sum())
        fast = moe_ops.fast_rows(
            int(one[0]["x"].size), self.cfg.experts_per_token, held,
            self.cfg.num_experts)
        control_s = again(rounded(skewed), topk, one, want_s)
        lap("skewed_router")
        out["skewed_router"] = {
            "held_columns_times": SKEW,
            **{k: skew[k] for k in (
                "nll_rms_err", "nll_rms_tol", "grad_fro_err",
                "expert_agree_share", "expert_gap_max", "expert_gap_tol",
                "ok")},
            "rows_layer0": rows, "fast_rows": fast,
            "second_part_ran": bool(rows > fast),
            "routing": routing(want_s[0], first, held),
            "control_8bit_nll_rms_err": control_s["nll_rms_err"]}
        out["seconds"] = seconds
        # at the rehearsal's sizes the controls prove nothing about the
        # chip's tolerances (and every row is in the first part): they
        # are reported there, and decide only a chip run
        if not self.cell.rehearse:
            out["ok"] = bool(
                out["ok"] and out["control_8bit"]["caught"]
                and out["control_dense_attention"]["caught"]
                and skew["ok"] and rows > fast)
        return out


def param_change(before, after) -> dict:
    """``|after - before| / |before|`` over the dense parameters (all
    but the embedding table) and over the table, host arrays."""
    import jax
    import numpy as np

    moved = {"dense": 0.0, "emb": 0.0}
    norm = dict(moved)
    after = jax.tree_util.tree_leaves(after)
    for (path, b), a in zip(jax.tree_util.tree_leaves_with_path(before),
                            after):
        group = "emb" if path[0].key == "emb" else "dense"
        moved[group] += float(np.sum(np.square(a - b, dtype=np.float64)))
        norm[group] += float(np.sum(np.square(b, dtype=np.float64)))
    return {g: float(np.sqrt(moved[g] / norm[g])) for g in moved}


def routing(want: dict, first: int, held: int) -> dict:
    """What the reference's router chose at the compared layer: how many
    tokens have 0, 1, 2 and 3 or more of their experts in the held
    range, and the mean over tokens of the largest gate over the
    smallest."""
    import numpy as np
    choice = want["expert_choice"]
    here = ((choice >= first) & (choice < first + held)).sum(axis=-1)
    p = np.take_along_axis(want["router_probs"], choice, axis=-1)
    return {"tokens_by_experts_here":
            [int((here == n).sum()) for n in (0, 1, 2)]
            + [int((here >= 3).sum())],
            "rows_here": int(here.sum()),
            "gate_max_over_min_mean": float(np.mean(p.max(-1) / p.min(-1)))}


def held_experts(got_choice, want_choice, first: int, held: int) -> list:
    """``[rows by the reference's routing, rows on which the two
    routings differ]`` for every held expert of the compared layer."""
    import numpy as np
    ids = np.arange(first, first + held)
    want_in = (want_choice[..., None] == ids).any(axis=-2)     # [N, held]
    got_in = (got_choice[..., None] == ids).any(axis=-2)
    return [[int(w), int(d)] for w, d in zip(
        want_in.sum(axis=0), (want_in != got_in).sum(axis=0))]


def _fro(gots, wants, k, *at):
    """Frobenius distance of the compared layer's gradient ``k`` (of
    its part ``at``), summed over the eval batches, over the
    reference's norm."""
    import numpy as np
    at = (COMPARE_LAYER, *at)
    ref = sum(w["grads"][k][at].astype(np.float64) for w in wants)
    got = sum(g["grads"][k][at].astype(np.float64) for g in gots)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _disputed(got_sel, want_sel, value, threshold, valid):
    """``[places in dispute, valid places]`` of two selections, and the
    largest distance of a disputed place's ``value`` from its row's
    ``threshold``."""
    import numpy as np
    differ = (got_sel != want_sel) & valid
    rows = differ.any(axis=-1)          # few rows hold a dispute
    gap = np.abs(value[rows] - threshold[rows])[differ[rows]]
    return [int(differ.sum()), int(valid.sum())], \
        float(gap.max()) if gap.size else 0.0


def compare(gots: list, wants: list, tol: dict, expert: int) -> dict:
    """The system's outputs against the reference's on the eval batches
    (the module's docstring, (a) to (c)); ``expert`` is the held expert
    whose ``w_gate`` gradient is reported. ``precision_ok`` holds (a)
    and (b), which the 8-bit control must fail; ``ok`` holds all three
    as far as ``gots`` bring them (``selection``, ``expert_choice``)."""
    import numpy as np

    d = np.concatenate([g["nll"].astype(np.float64) - w["nll"]
                        for g, w in zip(gots, wants)])
    nll_rms = float(np.sqrt(np.mean(d * d)))
    grad_fro = {"wq": _fro(gots, wants, "wq"),
                "w_gate": _fro(gots, wants, "w_gate", expert),
                "idx_wq": _fro(gots, wants, "idx_wq")}
    out = {"system_nll": float(np.mean([g["nll"] for g in gots])),
           "reference_nll": float(np.mean([w["nll"] for w in wants])),
           "nll_rms_err": nll_rms,
           "nll_max_err": float(np.max(np.abs(d))),
           "grad_fro_err": grad_fro, **tol}
    out["precision_ok"] = bool(
        nll_rms <= tol["nll_rms_tol"]
        and all(grad_fro[k] <= t for k, t in tol["grad_fro_tol"].items()))
    out["ok"] = out["precision_ok"]

    def pooled(parts):
        counts, gaps = zip(*parts)
        differ, valid = np.sum(counts, axis=0)
        return 1.0 - differ / max(valid, 1), max(gaps)

    if "selection" in gots[0]:
        # (c) the keys a query attends, at the compared layer
        parts = []
        for g, w in zip(gots, wants):
            scores = w["scores"]
            T = scores.shape[-1]
            causal = np.tril(np.ones((T, T), bool))[None]
            last = np.where(w["selection"], scores, np.inf).min(
                axis=-1, keepdims=True)
            count, gap = _disputed(g["selection"], w["selection"], scores,
                                   last, causal)
            rms = float(np.sqrt(np.mean(np.square(scores, dtype=np.float64),
                                        where=causal)))
            parts.append((count, gap / rms if rms > 0 else 0.0))
        out["selection_agree_share"], out["selection_gap_max"] = \
            pooled(parts)
        out["ok"] = bool(
            out["ok"]
            and out["selection_agree_share"] >= tol["selection_agree_min"]
            and out["selection_gap_max"] <= tol["selection_gap_tol"])
    if "expert_choice" in gots[0]:
        # ... and the experts a token is routed to
        parts = []
        for g, w in zip(gots, wants):
            probs = w["router_probs"].astype(np.float64)
            n = np.arange(probs.shape[0])[:, None]
            want_e = np.zeros(probs.shape, bool)
            want_e[n, w["expert_choice"]] = True
            got_e = np.zeros(probs.shape, bool)
            got_e[n, g["expert_choice"]] = True
            last_p = np.where(want_e, probs, np.inf).min(axis=-1,
                                                         keepdims=True)
            parts.append(_disputed(got_e, want_e, probs, last_p,
                                   np.ones(probs.shape, bool)))
        out["expert_agree_share"], out["expert_gap_max"] = pooled(parts)
        out["ok"] = bool(
            out["ok"]
            and out["expert_agree_share"] >= tol["expert_agree_min"]
            and out["expert_gap_max"] <= tol["expert_gap_tol"])
    return out


def build(cell, seed: int) -> System:
    import parallax_tpu as parallax
    from parallax_tpu.models import keye_vl2

    cfg = model_config(cell)
    dep = cell.deployment
    model = keye_vl2.build_model(cfg)
    model.init_fn = router_in_copies(
        model.init_fn, int(dep["chips_sharing_a_layer"]), ROUTER_COPY_NOISE)
    sess, *_ = parallax.parallel_run(
        model,
        parallax_config=parallax.Config(
            run_option=dep["run_option"], sparse_grad_mode="slices",
            search_partitions=bool(dep["search_partitions"]),
            shape_buckets=[int(cell.mix["global_batch"])]),
        num_partitions=cell.chips, seed=int(seed))
    reference = cell.plugin("reference", cell.config_name)
    return System(cell, sess, cfg, reference)
