"""Fused boosting iteration: ONE device program per boosting step.

The staged training loop (models/gbdt.py train_one_iter) submits a chain
of separately jitted entries per iteration — objective gradients, the
grow program (histogram waves + FindBestThreshold + partition), then the
partition-side score update — with host Python between the submissions.
Each hop is asynchronous, but the host glue between them (gradient
reshapes, learner padding, `.at[].set` staging) is real wall time that
scales with Python overhead, not with the device: at flagship shapes it
is the dominant share of `host_orchestration_s` (the schema-11 `iter`
field that makes the cost visible).

This module fuses the whole step into a single jitted entry:

    score -> get_gradients -> pad -> grow (lax.while_loop over the leaf
    frontier, ops/wave.py or ops/grow.py core) -> leaf partition ->
    score += clip(scale * leaf_value)[leaf_id]

so the host's per-iteration job collapses to one dispatch.  The
accelerator-GBDT literature (PAPERS.md: arxiv 2011.02022's pipelined
stage dataflow, 1706.08359's on-device leaf loop) gets its headline win
from exactly this collapse.

Bit-identity contract
---------------------
The fused program traces the SAME functions the staged path calls:

* gradients: ``objective.get_gradients`` (pure jnp for every built-in
  objective) followed by the same ``astype``/pad ops train_device does;
* growth: the learner's OWN jitted grow closure (``learner._grow``) is
  inlined — same statics, same kernels, same reduction orders, including
  the CPU-interpret Pallas path under ``tpu_pallas_interpret=true``;
* score update: ops/predict.py ``score_update_traced`` with the
  booster's own ``tpu_score_update`` engine — the gather form of
  ops/partition.py ``score_update_impl`` or, where the staged chain
  dispatches it (a TPU, an f32 score, at most 512 leaves), the Pallas
  compare-select kernel, which selects the same clipped f32 values.

Same trees, same split-audit events, same model file — enforced by
tests/test_fused_iter.py across the flagship/epsilon/msltr/expo_cat
shape buckets.

Eligibility (models/gbdt.py _resolve_fused_iter): serial learner, one
tree per iteration, a built-in (traceable) objective, no custom
gradients, no GOSS/DART gradient rescale, no gradient health staging.
Everything else falls back to the staged chain; ``tpu_fused_iter``
(auto/on/off) picks between them (auto: ops/plan.py Plan.fused_wanted).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .predict import score_update_traced
from ..obs import timers
from ..utils.log import Log


def fused_supported(booster) -> tuple:
    """(ok, reason) — can this booster's iteration be fused?

    Pure bookkeeping checks; the trace check (can the objective actually
    be staged into a jit?) happens in FusedIteration.build.
    """
    from ..models.gbdt import GBDT
    from .learner import SerialTreeLearner
    if type(booster).train_one_iter is not GBDT.train_one_iter:
        return False, "boosting mode overrides train_one_iter (dart)"
    if type(booster)._bagging_with_grad is not GBDT._bagging_with_grad:
        return False, "gradient-rescaling bagging (goss)"
    if booster.num_tree_per_iteration != 1:
        return False, "num_tree_per_iteration > 1 (multiclass)"
    if booster.objective is None:
        return False, "no built-in objective (custom fobj)"
    if type(booster.learner) is not SerialTreeLearner:
        return False, "distributed learner (mesh grow owns its dispatch)"
    if booster.learner._grow is None:
        return False, "learner has no serial grow program"
    obs = getattr(booster, "_obs", None)
    if obs is not None and getattr(obs, "health", None) is not None:
        # gradient health staging reads g/h between the stages the fused
        # program hides; keep the staged chain observable
        return False, "obs_health gradient staging needs staged g/h"
    return True, ""


class FusedIteration:
    """One boosting step as one jitted device entry.

    Built once per booster (the grow closure and objective are fixed for
    a training run); ``run`` submits a single program and returns the
    same (TreeArrays, leaf_id, new_score) triple the staged chain
    produces across its three entries.

    Every dataset-sized array — the bin matrix X, its transpose Xt, the
    objective's labels / weights / per-row tables — is an ARGUMENT of
    the program, never a closed-over constant: a literal would scale
    compile time, the size and key of every compile-cache entry and a
    second HBM copy with the dataset.
    """

    def __init__(self, learner, objective, num_data: int,
                 score_engine: str = "gather"):
        self._learner = learner
        self._num_data = int(num_data)
        self._obj_arrays, rebind = objective.split_device_state()
        pad = int(learner._row_pad)
        dtype = learner.dtype
        grow = learner._grow

        def step(X, Xt, obj_arrays, score, row_mult, feature_mask, scale):
            # stage 1: objective gradients in-graph — same ops the staged
            # path dispatches as its own entry (reshape to (1, N) and the
            # [0] slice are identities at k=1, so they are elided)
            with jax.named_scope("gradients"):
                g, h = rebind(obj_arrays).get_gradients(score)
                g = jnp.asarray(g, dtype)
                h = jnp.asarray(h, dtype)
                if pad:
                    z = jnp.zeros(pad, dtype)
                    g = jnp.concatenate([g, z])
                    h = jnp.concatenate([h, z])
            # stage 2: the learner's own grow program, inlined — the
            # lax.while_loop over the leaf frontier (hist accumulation,
            # FindBestThreshold, row->leaf partition) never touches host
            if Xt is None:
                tree, leaf_id = grow(X, g, h, row_mult, feature_mask)
            else:
                tree, leaf_id = grow(X, g, h, row_mult, feature_mask, Xt)
            # stage 3: partition-side score update, the staged chain's
            # own engine (bit-identity single source)
            with jax.named_scope("score_update"):
                if pad:
                    leaf_id = leaf_id[: self._num_data]
                new_score = score_update_traced(
                    score, leaf_id, tree.leaf_value, scale, score_engine)
            return tree, leaf_id, new_score

        self._step = jax.jit(step)
        # the step compiled ahead of time, one executable per argument
        # signature: holding the Compiled is what lets its own HLO text be
        # read (timers.scoped_executable) at no second compile
        self._compiled = {}

    def step_args(self, score, row_mult, feature_mask, scale) -> tuple:
        """The positional arguments of the jitted step, in order."""
        lrn = self._learner
        return (lrn.X, lrn._Xt, self._obj_arrays, score, row_mult,
                feature_mask, scale)

    @classmethod
    def build(cls, learner, objective, num_data: int, score_dtype,
              score_engine: str = "gather"):
        """Construct and trace-check the fused program.

        jax.eval_shape traces without compiling or executing, so a
        program that cannot trace fails here, once, cheaply.  A built-in
        objective that does not trace is a bug and raises.  Only a
        user-defined ObjectiveFunction subclass whose get_gradients runs
        host code on the score (numpy on a tracer: a JAXTypeError) sends
        the booster back to the staged chain, with a warning."""
        fused = cls(learner, objective, num_data, score_engine)
        n = int(num_data)
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            fused.step_args(
                jax.ShapeDtypeStruct((n,), score_dtype), learner._ones,
                jax.ShapeDtypeStruct(
                    (max(learner.train_data.num_features, 1),), jnp.bool_),
                jax.ShapeDtypeStruct((), score_dtype)))
        try:
            jax.eval_shape(fused._step, *shapes)
        except jax.errors.JAXTypeError as e:
            from ..objectives import ObjectiveFunction
            if type(objective).__module__ == ObjectiveFunction.__module__:
                raise
            Log.warning("tpu_fused_iter: objective %s does not trace into "
                        "the fused program (%s); using the staged "
                        "iteration chain", type(objective).__name__, e)
            return None
        return fused

    def run(self, score, row_mult, feature_mask, scale):
        """Submit the fused step.  Mirrors train_device's host-side prep
        (row_mult default + pad) so the two paths see identical inputs;
        no host synchronization anywhere."""
        lrn = self._learner
        if row_mult is None:
            row_mult = lrn._ones
        else:
            row_mult = jnp.asarray(row_mult, lrn.dtype)
            if lrn._row_pad:
                row_mult = jnp.concatenate(
                    [row_mult, jnp.zeros(lrn._row_pad, lrn.dtype)])
        if feature_mask is None:
            feature_mask = lrn.sample_feature_mask()
        obs = lrn._obs
        args = self.step_args(score, row_mult, feature_mask, scale)
        obs.entry_args("fused_iter", self._step, args,
                       names=("X", "Xt", "objective", "score", "row_mult",
                              "feature_mask", "scale"))
        t0 = obs.entry_start()
        with timers.span("dispatch"):
            tree, leaf_id, new_score = timers.scoped_executable(
                self._step, self._compiled, args)(*args)
        obs.entry_end("fused_iter", t0, (tree, leaf_id, new_score))
        return tree, leaf_id, new_score
