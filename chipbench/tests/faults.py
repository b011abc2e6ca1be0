"""Faults planted under the timed path, for the tests that see
``correct`` come out false.  Each is a context manager over a
monkeypatch of the program."""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def patched(obj, name, fn):
    old = getattr(obj, name)
    setattr(obj, name, fn(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def _host_fault(change):
    from repro.exec.plan import PlanResult

    def wrap(old):
        def host(self):
            return change(np.array(old(self)))
        return host

    return patched(PlanResult, "host", wrap)


def answer_altered():
    """One symbol of every planned GF result changed where it is made."""
    def change(out):
        out.reshape(-1)[0] = (out.reshape(-1)[0] + 1) % 257
        return out
    return _host_fault(change)


def half_left_out():
    """Every planned GF result with its second half of columns zeroed."""
    def change(out):
        out[..., out.shape[-1] // 2:] = 0
        return out
    return _host_fault(change)


def save_unchanged():
    """Each save commits the state of the first one it was given."""
    from repro.core import placement

    def wrap(old):
        first = []

        def to_blocks(tree, n, p=257):
            if not first:
                first.append(old(tree, n, p))
            return first[0]
        return to_blocks

    return patched(placement, "pytree_to_blocks", wrap)


def shards_left_out():
    """Each state leaf that spans several chips read from its first
    chip alone: the exchange from the other chips left out, their
    shards saved as zeros."""
    import jax
    from repro.core import placement

    def first_chip(x):
        if not isinstance(x, jax.Array) or len(x.sharding.device_set) == 1:
            return x
        out = np.zeros(x.shape, x.dtype)
        shard = x.addressable_shards[0]
        out[shard.index] = np.asarray(shard.data)
        return out

    def wrap(old):
        def to_bytes(tree):
            return old(jax.tree_util.tree_map(first_chip, tree))
        return to_bytes

    return patched(placement, "pytree_to_bytes", wrap)


def restore_unchanged():
    """A restore that rebuilds the lost node but never writes it back."""
    from repro.checkpoint.msr_checkpoint import MSRCheckpointer
    return patched(MSRCheckpointer, "_write_node_pair",
                   lambda old: lambda self, *a, **k: None)


UNCHANGED = {"ckpt.save": save_unchanged,
             "ckpt.restore-regen": restore_unchanged,
             "ckpt.save-host4": save_unchanged}
