"""Oracle for the flash attention kernel: the model's own chunked
online-softmax attention, which the kernel replaces on the TPU."""
from __future__ import annotations

from ...models.attention import attend_chunked

__all__ = ["attend_chunked"]
