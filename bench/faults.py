"""Faults a training cell can have, planted in the timed path underneath a
run: the check has to read each as not correct. Used by the tests at smoke
size and by bench/control.py at the cell's own size.

  frozen       the step returns its state unchanged
  half_batch   half of the batch left out (half of the rows, or of the
               positions of a single row), the loss the mean over the rest
"""

from __future__ import annotations


def plant(fault: str, train_bench) -> None:
    """Break `train_bench.Run` in place; a step compiled afterwards carries
    the fault."""
    import jax
    import numpy as np

    run = train_bench.Run
    if fault == "frozen":
        real = run.step

        def step(self, state, batch):
            copy = jax.tree.map(lambda x: x.copy(), state)
            return (*state, real(self, copy, batch)[-1])

        run.step = step
    elif fault == "half_batch":
        real_feed = run.feed

        def feed(self, step):
            b = jax.device_get(real_feed(self, step))
            valid = np.array(b["valid"])
            if valid.shape[0] > 1:
                valid[valid.shape[0] // 2:] = 0
            else:
                valid[:, valid.shape[1] // 2:] = 0
            return jax.device_put(dict(b, valid=valid), self.batch_shardings)

        run.feed = feed
    else:
        raise ValueError(f"unknown fault {fault!r}")
