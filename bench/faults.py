"""Faults planted under the timed path, to show that `correct` catches them.

  unchanged    the step returns the state it was given (its loss still
               computed);
  half_batch   the loss is the mean over the first half of each worker's
               rows, the other half left out; a worker with one row keeps
               the first half of its sequence;
  no_exchange  the all-gather between workers hands each worker only its
               own payload, so every worker steps on its own gradient.

Only the fault runs and tests use this module; `run.py` never plants one
unless asked with --mode.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NAMES = ("unchanged", "half_batch", "no_exchange")


def plant(name: str):
    """Patch the program for `name`; returns a function that undoes it."""
    if name == "half_batch":
        from repro.models import model as model_lib
        orig = model_lib.loss_fn

        def half(cfg, params, batch):
            tokens = batch["tokens"]
            rows, cols = tokens.shape[:2]
            if rows > 1:
                return orig(cfg, params, {"tokens": tokens[:rows // 2]})
            return orig(cfg, params, {"tokens": tokens[:, :cols // 2 + 1]})

        model_lib.loss_fn = half
        return lambda: setattr(model_lib, "loss_fn", orig)
    if name == "no_exchange":
        orig = jax.lax.all_gather

        def own_only(x, axis_name, *, axis=0, tiled=False, **kw):
            axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
            size = math.prod(jax.lax.axis_size(a) for a in axes)
            out = jnp.stack([x] * size, axis=axis)
            return out.reshape((-1,) + out.shape[2:]) if tiled else out

        jax.lax.all_gather = own_only
        return lambda: setattr(jax.lax, "all_gather", orig)
    if name in ("unchanged", None):
        return lambda: None
    raise ValueError(f"unknown fault {name!r}; known: {NAMES}")


def wrap_step(name: str, step):
    if name != "unchanged":
        return step

    def unchanged(params, opt_state, ef, batch):
        return params, opt_state, ef, step(params, opt_state, ef, batch)[3]

    return unchanged
