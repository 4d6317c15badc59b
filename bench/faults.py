"""Faults planted in the program's timed path, to show that ``correct``
catches them.  Benchmark runs never import this module; the limits are
set with it (``bench/calibrate.py --fault``) and the CPU tests drive it
(``tests/bench/bench_drive.py``).

  none       the program as it is
  unchanged  the train step returns its state unchanged
  half       half of the kept sequences are left out, the mean taken
             over the rest
  prox       the dual z is updated, the stored weights are not: the
             optimizer returns the parameters it was given
"""
from __future__ import annotations

FAULTS = ("none", "unchanged", "half", "prox")


def plant(fault: str) -> None:
    """Break the program as ``fault`` says; call before it is built."""
    import jax.numpy as jnp
    import repro.api.protocol as protocol
    import repro.models as models
    from repro.optim.optimizers import DualAveragingOpt

    if fault == "unchanged":
        exact = protocol.make_train_step

        def make_train_step(*a, **k):
            step = exact(*a, **k)

            def frozen(params, opt_state, batch, b):
                return (params, opt_state) + step(params, opt_state, batch, b)[2:]
            return frozen
        protocol.make_train_step = make_train_step
    elif fault == "half":
        loss = models.lm_loss

        def lm_loss(params, cfg, batch, seq_weights=None):
            w = seq_weights
            if w is not None:       # keep every other kept sequence
                w = w * (jnp.cumsum(w) % 2 == 1)
            return loss(params, cfg, batch, w)
        models.lm_loss = lm_loss
    elif fault == "prox":
        apply = DualAveragingOpt.apply

        def skip_prox(self, grads, state, params, shardings=None):
            return params, apply(self, grads, state, params, shardings)[1]
        DualAveragingOpt.apply = skip_prox
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}; choose from {FAULTS}")
