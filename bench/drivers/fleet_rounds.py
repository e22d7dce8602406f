"""Whole fleet rounds, repeated until the window's time has passed.

Each round is one ``FedCCL.run(rounds=1)``: with no masker the
asynchronous simulated runtime (coalescing drains at fetch time and at
``max_coalesce``), with secure aggregation the lockstep secure rounds.
The window closes at the end of the first round that ends after
``--seconds``, once every folded model is ready on the device.
"""

from federation import Federation


def make(ctx):
    return Federation(ctx)
