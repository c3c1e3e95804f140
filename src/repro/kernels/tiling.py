"""Block-size choice shared by the Pallas kernels' wrappers.

The TPU compiler accepts a block whose last two dims are multiples of
(8, 128) or equal to the array's own dims, and every kernel here walks
its grid with blocks that must divide the array.  ``pick_block`` finds
such a block or says why there is none — never a silent fallback."""
from __future__ import annotations

# Bytes of VMEM the wrappers let one kernel's streamed, double-buffered
# input tiles take: half of the compiler's default 16 MiB scoped limit,
# leaving the rest for the kernel's own temporaries.
VMEM_TILE_BUDGET = 8 << 20


def pick_block(n: int, cap: int, align: int, *, what: str = "dim") -> int:
    """Largest multiple of ``align`` that is at most ``cap`` and divides
    ``n``; ``n`` itself when ``n <= cap`` (a full-extent block is always
    legal).  Raises ``ValueError`` when no such block exists."""
    if n <= cap:
        return n
    for b in range(cap - cap % align, 0, -align):
        if n % b == 0:
            return b
    raise ValueError(f"no block of {what}={n} is a multiple of {align}, "
                     f"at most {cap}, and divides it; pad {what} to a "
                     f"multiple of {align} or call with use_kernel=False")
