"""Per-platform choice between a Pallas TPU kernel and its alternatives.

The choice is made for the platform a program is LOWERED for, through
``jax.lax.platform_dependent``, not from the process's default backend.  A
program placed on a CPU device inside a process whose default backend is
the TPU (the host-side reference a chip run compares against) therefore
takes the CPU path, and a program compiled for a described TPU from a
CPU-only process takes the compiled-kernel path.  Only the branch of the
lowering platform reaches the compiler, so the choice costs nothing at run
time.

Two tri-state arguments resolve here:

* ``interpret=None`` — the kernel is compiled on TPU and run by the Pallas
  interpreter everywhere else; ``True``/``False`` force one or the other.
* ``use_kernel=None`` — for ops with a bit-identical XLA lowering: the
  kernel on TPU, the XLA lowering everywhere else.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax


def run_pallas(call: Callable[..., jax.Array], *args,
               interpret: Optional[bool] = None):
    """``call(*args, interpret=...)``: compiled when lowered for TPU,
    interpreted on every other platform, unless ``interpret`` says."""
    if interpret is not None:
        return call(*args, interpret=interpret)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


def kernel_or_xla(kernel: Callable, xla: Callable, *args,
                  use_kernel: Optional[bool] = None):
    """``kernel(*args)`` when lowered for TPU, ``xla(*args)`` elsewhere,
    unless ``use_kernel`` says."""
    if use_kernel is None:
        return jax.lax.platform_dependent(*args, tpu=kernel, default=xla)
    return (kernel if use_kernel else xla)(*args)
