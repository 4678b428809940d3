"""Faults planted in the program's timed path, underneath the harness,
to show that the correctness check catches each one a training cell can
have. Used by ``readings.py`` on the chip and by the CPU tests; the
benchmark's own runs never plant one.

- ``state_unchanged``: the step computes, then hands back the state it
  was given.
- ``half_batch``: the step sees only the first half of the batch's rows,
  so its mean is taken over the rest.
- ``input_flipped``: the step sees every image mirrored left to right:
  the same numbers in other places, which moves the BN statistics'
  direction more than their norms.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "input_flipped")


@contextlib.contextmanager
def planted(fault):
    """Within the block, ``build_job`` builds the program with ``fault``
    (None plants nothing)."""
    import jax.numpy as jnp
    import repro.launch.train as train

    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    saved_jit = train.jit_train_step

    def jit_broken(step, **kw):
        def broken(state, batch):
            if fault == "state_unchanged":
                return state, step(state, batch)[1]
            if fault == "input_flipped":
                images = batch["images"]
                if jnp.ndim(images) != 4:
                    raise ValueError("input_flipped needs NHWC images, got "
                                     f"shape {jnp.shape(images)}")
                return step(state, {**batch,
                                    "images": jnp.flip(images, axis=2)})
            half = {k: (v[:v.shape[0] // 2] if jnp.ndim(v) else v)
                    for k, v in batch.items()}
            return step(state, half)
        return saved_jit(broken, **kw)

    try:
        train.jit_train_step = jit_broken
        yield
    finally:
        train.jit_train_step = saved_jit
