"""Faults planted under the timed path, to show that ``correct`` fails
on them: each takes the program's built cell and returns a broken step
with the step's own signature. Used by the CPU tests and, at a cell's
own size on the chip, by ``calibrate.py --fault-seeds``."""
from __future__ import annotations

import functools

import jax


def state_unchanged(built):
    """The step computes its loss but returns the parameters and
    optimizer state it was given, inside one compiled program that
    donates them as the program's step does (so it fits where the
    step fits)."""
    inner = built.step.__wrapped__

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(params, opt, batch, bits):
        _, _, loss, metrics = inner(params, opt, batch, bits)
        return params, opt, loss, metrics
    return run


def half_batch(built):
    """Half of each node's batch left out, the mean taken over the rest:
    half of the rows, or of the positions where a node has one row."""
    step = built.step

    def run(params, opt, batch, bits):
        rows = batch["tokens"].shape[1]
        if rows > 1:
            half = {k: v[:, : rows // 2] for k, v in batch.items()}
        else:
            half = {k: v[:, :, : v.shape[2] // 2] for k, v in batch.items()}
        return step(params, opt, half, bits)
    return run


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
