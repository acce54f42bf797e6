"""Room on CPython's frame stack for a thread that traces programs.

CPython (3.11, 3.12) keeps a thread's interpreter frames in 16 KiB chunks
and unmaps a chunk the moment the frame at its base returns. Where a chunk
ends in the middle of a hot call chain, every call that crosses the end maps
16 KiB, faults its first page in and unmaps it again. Tracing a serving
program is such a chain, a hundred frames deep under the scheduler loop, and
which call crosses depends on the size of every frame above it: a local
variable more in a kernel's body moved a 32-layer prefill step's trace from
12,065 to 28,311 such cycles (counted ``munmap`` calls), 5.6 to 8.3 s on the
chip machine's host, with not one Python call more (PERF.md, PR 31).

``with_deep_stack(fn)`` calls ``fn`` from a frame that asks for ``SLOTS``
slots of evaluation stack it never uses. CPython gives such a frame a chunk
of its own, twice its size, which stays mapped until ``fn`` returns; every
frame beneath it lands in the chunk's other half and no call crosses an end.
"""

from __future__ import annotations

import types

SLOTS = 16384       # 128 KiB of frame, a 256 KiB chunk: the loops run ~4k deep


def _call(fn):
    return fn()


_call_with_room = types.FunctionType(
    _call.__code__.replace(co_stacksize=SLOTS), globals(), "with_deep_stack")


def with_deep_stack(fn):
    """``fn()``, from a frame with ``SLOTS`` spare stack slots."""
    return _call_with_room(fn)
