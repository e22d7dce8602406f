"""Dispatcher that neither imports the kernel module nor calls
interpret_mode (FED303 x2), and whose public function drops the oracle's
``alpha`` parameter (FED302)."""


def scale(x, beta=2.0):
    return [v * beta for v in x]
