"""jit'd public wrapper used by repro.models.ssm (use_kernel=True path)."""
from __future__ import annotations

import functools

import jax

from . import ref
from .kernel import ssd_intra_chunk_kernel


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def ssd_intra_chunk(xc, dtc, la, Bc, Cc, *, use_kernel: bool = True,
                    interpret: bool | None = None):
    if not use_kernel:
        return ref.ssd_intra_chunk(xc, dtc, la, Bc, Cc)
    return ssd_intra_chunk_kernel(xc, dtc, la, Bc, Cc, interpret=interpret)
