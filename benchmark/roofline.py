"""Bytes each device kernel's call must move, from its argument and result
shapes (``ops/propagate.py`` at commit 9028760).

``_draw_kernel``: packed (4, P) uint32 in, the (2,) uint32 seed key in,
P bit-packed flags out (P / 8 bytes). ``_min_draw_kernel``: packed (3, P)
uint32 in, the key in, (P,) uint32 out. P is the padded bucket of the
call. The loss draw is 20 rounds of Threefry-2x32 over 10 packet lanes
per unit, integer work on the vector unit, for which the v5e publishes
no peak: the bytes over HBM bandwidth are the only bound on its least
time that a published number gives.
"""

from __future__ import annotations

U32 = 4
KEY_BYTES = 2 * U32


def draw_kernel_bytes(p: int) -> int:
    return 4 * p * U32 + KEY_BYTES + -(-p // 8)


def min_draw_kernel_bytes(p: int) -> int:
    return 3 * p * U32 + KEY_BYTES + p * U32


#: kernel name -> bytes of one call at padded bucket P
KERNELS = {"_draw_kernel": draw_kernel_bytes,
           "_min_draw_kernel": min_draw_kernel_bytes}
