"""Execution mode shared by every Pallas kernel in this tree: interpret or
compile, decided from the platform, and 32-bit tracing for compiled calls."""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → compile for the TPU when the default backend is one,
    otherwise run the Pallas interpreter. An explicit bool is kept, so an
    ahead-of-time TPU compile on a CPU host passes ``interpret=False``."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


class TPUUnsupportedError(NotImplementedError):
    """A kernel that has no compiled TPU form was asked to compile."""


def interpret_only(name: str, interpret: bool | None, why: str) -> bool:
    """:func:`resolve_interpret` for a kernel the TPU compiler refuses:
    raise :class:`TPUUnsupportedError` (naming ``why``) where it would
    compile, rather than interpret or fall back without a word."""
    if not resolve_interpret(interpret):
        raise TPUUnsupportedError(f"{name} has no compiled TPU form: {why}")
    return True


def pallas_call(kernel, *, interpret: bool | None = None, **kw):
    """``pl.pallas_call`` with ``interpret`` resolved by
    :func:`resolve_interpret`. A compiled (TPU) kernel is traced with 32-bit
    default types: Mosaic compiles no 64-bit value, and under the solver's
    process-wide x64 switch the Python scalars of a kernel body or index map
    would trace as 64-bit (the TPU compiler then aborts the process)."""
    interpret = resolve_interpret(interpret)
    call = pl.pallas_call(kernel, interpret=interpret, **kw)
    if interpret:
        return call

    def call_x32(*args):
        with jax.enable_x64(False):
            return call(*args)

    return call_x32
