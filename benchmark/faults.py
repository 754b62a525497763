"""The control and the faults that `correct` has to catch.

Each is a context manager that breaks the timed path underneath the
driver, which then runs as ever; `correct` has to come out false.  The
tests drive them tiny on the CPU, ``control.py`` at a cell's own size on
the chip.  A benchmark run never uses them.

fp8_products      the control: the histograms' products in float8 (e4m3),
                  the nearest precision below the bf16 products that the
                  configuration's histograms are built from.  Gradients
                  and hessians are rounded where each histogram engine
                  takes them (root pass, wave pass, Pallas kernels); the
                  objective, the leaves' totals and the score update keep
                  float32, as they do beside the program's own bf16.
                  (Rounding the gradients where the objective makes them
                  is no control: every sum is then exact in bf16, and it
                  reads nearer to the reference than the program as it
                  is, PERF.md section 4)
half_batch        every odd row left out of the histograms, leaf values
                  taken over the rest
state_unchanged   the second step hands back the score it was given
answer_altered    the first tree's root threshold moved where the trees
                  are handed over
"""
import contextlib


@contextlib.contextmanager
def _patched(owner, name, new):
    old = getattr(owner, name)
    setattr(owner, name, new)
    try:
        yield
    finally:
        setattr(owner, name, old)


@contextlib.contextmanager
def _all_patched(patches):
    """Several attributes swapped at once; JAX's traces are dropped going
    in and coming out, because a function traced before the swap would be
    served from the cache and never see it."""
    import jax

    with contextlib.ExitStack() as stack:
        for owner, name, new in patches:
            stack.enter_context(_patched(owner, name, new))
        jax.clear_caches()
        try:
            yield
        finally:
            jax.clear_caches()


def _rounding_argument(fn, index, transform):
    def wrapped(*args, **kwargs):
        args = list(args)
        args[index] = transform(args[index])
        return fn(*args, **kwargs)
    return wrapped


def fp8_products():
    import jax.numpy as jnp
    from lightgbm_tpu.ops import histogram, pallas_wave, wave

    def q(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    # (module, function, position of its weights argument)
    sites = [(histogram, "_onehot_accumulate", 1),
             (histogram, "_scatter_accumulate", 1),
             (wave, "_slot_hist", 2),
             (pallas_wave, "wave_histogram_pallas_t", 2),
             (pallas_wave, "wave_histogram_pallas", 2),
             (pallas_wave, "wave_partition_hist_pallas_ct", 2)]
    return _all_patched([
        (mod, name, _rounding_argument(getattr(mod, name), at, q))
        for mod, name, at in sites])


def _gradient_fault(transform):
    from lightgbm_tpu.objectives import BinaryLogloss

    orig = BinaryLogloss.get_gradients

    def get_gradients(self, score):
        g, h = orig(self, score)
        return transform(g), transform(h)

    return _patched(BinaryLogloss, "get_gradients", get_gradients)


def half_batch():
    import jax.numpy as jnp

    return _gradient_fault(
        lambda x: jnp.where(jnp.arange(x.shape[0]) % 2 == 0, x, 0.0))


def state_unchanged():
    import lightgbm_tpu as lgb

    orig = lgb.Booster.update
    calls = {"n": 0}

    def update(self, *args, **kwargs):
        calls["n"] += 1
        before = self._gbdt._score_dev
        out = orig(self, *args, **kwargs)
        if calls["n"] == 2:
            self._gbdt._score_dev = before
            self._gbdt._invalidate_train()
        return out

    return _patched(lgb.Booster, "update", update)


def answer_altered():
    from lightgbm_tpu.models.gbdt import GBDT

    orig = GBDT._materialize
    done = {"n": 0}

    def _materialize(self):
        orig(self)
        if not done["n"] and self.models:
            done["n"] = 1
            tree = self.models[0]
            tree.threshold_in_bin[0] = (int(tree.threshold_in_bin[0]) + 2) % 60
    return _patched(GBDT, "_materialize", _materialize)


FAULTS = {"fp8_products": fp8_products, "half_batch": half_batch,
          "state_unchanged": state_unchanged,
          "answer_altered": answer_altered}
