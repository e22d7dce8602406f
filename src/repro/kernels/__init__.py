"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package has three modules:
  <name>.py  — the pl.pallas_call kernel with explicit BlockSpec VMEM tiling
  ops.py     — the public wrapper (padding, flattening, dispatch)
  ref.py     — the pure-jnp oracle the kernel is validated against

Kernels target the TPU (MXU/VPU-aligned tiles, scalars in SMEM).  Each
``ops.py`` resolves its mode through :func:`interpret_mode` when it is
called: compiled on an accelerator backend, the Pallas interpreter on the
CPU backend.  The ``interpret=`` argument of every ``ops.py`` function is
the one override (tests use it to pin a mode).
"""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a kernel call runs in the Pallas interpreter.

    An explicit ``interpret`` wins; ``None`` follows the default backend:
    interpreted on ``"cpu"``, compiled everywhere else."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"
