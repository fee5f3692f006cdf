"""The one rule that turns a kernel ``impl`` request into an implementation.

``auto`` picks the compiled Pallas kernel on a TPU when the program runs on
one device, and the XLA path everywhere else: a Mosaic kernel has no
partitioning rule, so a program sharded over several devices takes the
XLA path, which GSPMD partitions.  ``pallas`` always means the kernel.  It
is compiled for the TPU unless the caller asks for interpret mode; on any
other backend without that request it is an error, never a silent switch
to the interpreter.
"""
from __future__ import annotations

import jax

IMPLS = ("auto", "xla", "pallas")


def resolve_impl(impl: str, *, interpret: bool = False,
                 n_devices: int = 1) -> str:
    if impl not in IMPLS:
        raise ValueError(f"kernel impl must be one of {IMPLS}, got {impl!r}")
    backend = jax.default_backend()
    if impl == "auto":
        return "pallas" if backend == "tpu" and n_devices == 1 else "xla"
    if impl == "pallas" and not interpret and backend != "tpu":
        raise ValueError(
            f"impl='pallas' compiles the TPU kernel, but the backend is "
            f"{backend!r}: pass interpret=True to run it in interpret mode")
    return impl
