"""Faults planted under the timed path, for the tests that show `correct`
fails on them and for the control readings on the chip.

``plant(name)`` breaks the program in this process and returns a function
that repairs it.
"""

from __future__ import annotations

import numpy as np


def _clear_train_engine():
    from repro.core import train

    train._engine_fns.cache_clear()


def _swap(obj, attr, new, clear=None):
    old = getattr(obj, attr)
    setattr(obj, attr, new)
    if clear:
        clear()

    def undo():
        setattr(obj, attr, old)
        if clear:
            clear()
    return undo


def state_unchanged():
    """Every training step returns the state it was given."""
    from repro.core import train

    real = train.apply_gradients

    def frozen(state, grads, opt):
        _, metrics = real(state, grads, opt)
        return state._replace(step=state.step + 1), metrics

    return _swap(train, "apply_gradients", frozen, _clear_train_engine)


def half_batch():
    """The loss is taken over the first half of the batch only."""
    from repro.core import train

    real = train.info_nce

    def half(z1, z2, tau):
        b = z1.shape[0] // 2
        return real(z1[:b], z2[:b], tau)

    return _swap(train, "info_nce", half, _clear_train_engine)


def loss_altered():
    """The loss comes out 1% high where it is produced."""
    from repro.core import train

    real = train.info_nce

    def altered(z1, z2, tau):
        loss, metrics = real(z1, z2, tau)
        return loss * 1.01, metrics

    return _swap(train, "info_nce", altered, _clear_train_engine)


def embedding_altered():
    """One feature of every kernel embedding moves by 5% of the largest."""
    from repro.sampling.methods import GCLMethod

    real = GCLMethod.prepare

    def altered(self, program):
        art = real(self, program)
        emb = np.array(art.payload["embeddings"])
        emb[:, 0] += 0.05 * np.abs(emb).max()
        art.payload["embeddings"] = emb
        return art

    return _swap(GCLMethod, "prepare", altered)


def representative_altered():
    """Each cluster's representative is its last member, not its first."""
    from repro.sampling import engine

    real = engine.plan_from_labels

    def last_member(labels, seqs, method, extra=None, **kw):
        plan = real(labels, seqs, method, extra, **kw)
        labels = np.asarray(labels)
        plan.reps = {c: [int(np.nonzero(labels == c)[0][-1])]
                     for c in plan.reps}
        return plan

    return _swap(engine, "plan_from_labels", last_member)


def labels_shuffled():
    """Each plan's cluster labels are permuted over its invocations before
    the representatives are taken from them."""
    from repro.sampling import engine

    real = engine.plan_from_labels

    def shuffled(labels, seqs, method, extra=None, **kw):
        labels = np.random.default_rng(0).permutation(np.asarray(labels))
        return real(labels, seqs, method, extra, **kw)

    return _swap(engine, "plan_from_labels", shuffled)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, loss_altered,
                                  embedding_altered, representative_altered,
                                  labels_shuffled)}


def plant(name: str):
    return FAULTS[name]()
