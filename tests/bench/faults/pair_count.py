"""Faults planted under the timed path of the ``pair_count`` driver.

Each takes a ``pytest.MonkeyPatch`` and patches the program for the length
of one run, which must then read ``correct`` false."""


def _pc_answer_altered(mp):
    from repro.core.dualtree import DualTree

    orig = DualTree.pair_count

    def bad(self, edges):
        hist, stats = orig(self, edges)
        hist = hist.copy()
        hist[len(hist) // 2] += 2
        return hist, stats

    mp.setattr(DualTree, "pair_count", bad)


def _pc_half_batch(mp):
    import repro.core.dualtree as dt

    orig = dt._pair_hist_kernel

    def half(*a):
        h = orig(*a)
        # the second half of every leaf-pair batch is left out
        return h.at[h.shape[0] // 2:].set(0)

    half._cache_size = orig._cache_size         # the program's audit
    mp.setattr(dt, "_pair_hist_kernel", half)


def _pc_state_unchanged(mp):
    import jax.numpy as jnp

    import repro.core.dualtree as dt

    orig = dt._pair_hist_kernel

    def nothing(*a):
        return jnp.zeros_like(orig(*a))

    nothing._cache_size = orig._cache_size      # the program's audit
    mp.setattr(dt, "_pair_hist_kernel", nothing)


FAULTS = [_pc_answer_altered, _pc_half_batch, _pc_state_unchanged]
