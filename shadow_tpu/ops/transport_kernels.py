"""Batched integer kernels for the device-resident columnar transport.

The third twin surface (PR 11): `network/transport.py` is the scalar
Python oracle, `native/colcore/colcore.c` the scalar C twin, and this
module the COLUMNAR twin — the same endpoint arithmetic expressed over
struct-of-arrays int64 columns, parameterized over the array namespace
(numpy or jax.numpy, the ops/prng.py discipline) so the numpy path and
the accelerator path execute the exact same integer operations.

Twin discipline: the transport constants and the per-congestion-control
integer literals below are DELIBERATE duplicates of the scalar twins —
like colcore.c, a kernel cannot import its constants from the module it
must agree with and still be audited for drift.  tools/twincheck
cross-checks all three surfaces (`kernel-const-drift:*` /
`kernel-cc-drift:*` findings); editing a literal in any one twin without
the other two fails CI by name.

Bit-exactness argument (why numpy/jax int64 equals scalar Python int):
every scalar operand is clamped below 2**63 by the transport's documented
clamps (cwnd <= 2**45, newly <= 2**20 in cubic, |d| <= 2e5, w_max capped
at 2**32 before the cube root), no division has a negative dividend in
the scalar twins, and numpy/jax floor division on int64 equals Python's
`//` wherever both are defined.  The cube root is a fixed-iteration
binary search (identical decisions to transport._icbrt's while-loop —
once lo == hi the invariant lo**3 <= x makes further iterations no-ops).
"""

from __future__ import annotations

import numpy as np

# -- shared transport constants (audited against transport.py + colcore.c
# by tools/twincheck; see the twin-discipline note above) -----------------
NS_PER_MS = 1_000_000
MSS = 1460
INIT_CWND = 10 * MSS
MIN_CWND = 2 * MSS

#: CongestionControl.cc_id dispatch values (transport.py registry twin)
CC_NEWRENO = 0
CC_CUBIC = 1

#: fixed iteration count for the vectorized cube root: ceil(log2(2**20))
#: + 1 covers transport._icbrt's full [0, 2**20] search interval
_ICBRT_ITERS = 21


def icbrt(x, xp=np):
    """Vectorized floor integer cube root — the exact batch twin of
    transport._icbrt (same binary search over [0, 2**20], fixed-trip).
    ``x`` is a non-negative int64 array with values < 2**60."""
    lo = xp.zeros_like(x)
    hi = xp.full_like(x, 1 << 20)
    one = xp.asarray(1, dtype=x.dtype)
    for _ in range(_ICBRT_ITERS):
        mid = (lo + hi + one) >> one
        ok = mid * mid * mid <= x
        lo = xp.where(ok, mid, lo)
        hi = xp.where(ok, hi, mid - one)
    return lo


def cc_on_ack(cc_id, cwnd, ssthresh, w_max, epoch_start, newly, now,
              xp=np):
    """Batched CongestionControl.on_ack over a cohort of endpoints: the
    columnar twin of NewReno.on_ack and CubicLike.on_ack dispatched on
    the ``cc_id`` column.  All inputs are int64 arrays of one cohort
    length; returns (cwnd', w_max', epoch_start').  ssthresh is read-only
    here (neither algorithm moves it on an ack) and passed for the
    slow-start test.

    Every arithmetic step below mirrors one line of the scalar twins —
    keep them in lockstep (twincheck audits the literal sets, the
    identity tests the results)."""
    ss = cwnd < ssthresh
    # slow start, shared shape: cwnd += min(newly, cwnd)
    cwnd_ss = cwnd + xp.minimum(newly, cwnd)

    # NewReno congestion avoidance: cwnd += max(1, MSS * newly // cwnd)
    cwnd_nr = cwnd + xp.maximum(
        xp.asarray(1, dtype=cwnd.dtype), MSS * newly // cwnd)

    # CubicLike congestion avoidance (first CA ack with no recorded
    # epoch adopts (now, cwnd) as the epoch — vectorized via where)
    es0 = epoch_start == 0
    eps = xp.where(es0, now, epoch_start)
    wmax = xp.where(es0, cwnd, w_max)
    t_ms = (now - eps) // NS_PER_MS
    wmax_c = xp.minimum(wmax, 1 << 32)
    k_ms = icbrt((wmax_c * 3 // (4 * MSS)) * 1_000_000_000, xp)
    d = xp.clip(t_ms - k_ms, -200_000, 200_000)
    a = xp.where(d < 0, -d, d)
    delta = (a * a * a // 1_000_000) * (4 * MSS) // 10_000
    target = xp.clip(xp.where(d < 0, wmax - delta, wmax + delta),
                     MIN_CWND, 1 << 45)
    nn = xp.minimum(newly, 1 << 20)
    one = xp.asarray(1, dtype=cwnd.dtype)
    inc = xp.minimum(target - cwnd, 1 << 40) * nn // cwnd
    below = xp.minimum(cwnd + xp.maximum(inc, one), target)
    creep = cwnd + xp.maximum(MSS * nn // (100 * cwnd), one)
    cwnd_cu = xp.where(cwnd < target, below, creep)

    cubic = cc_id == CC_CUBIC
    cwnd_out = xp.where(ss, cwnd_ss, xp.where(cubic, cwnd_cu, cwnd_nr))
    # cubic epoch adoption happens only on a cubic CA ack
    adopt = cubic & ~ss
    return (cwnd_out,
            xp.where(adopt, wmax, w_max),
            xp.where(adopt, eps, epoch_start))


def ack_advance(cc_id, cwnd, ssthresh, w_max, epoch_start, snd_una,
                bytes_acked, cum_ack, now, xp=np):
    """One clean cumulative-ack advance for a cohort: the batched twin of
    StreamSender.on_ack's strict-advance arithmetic (scoreboards empty,
    not in recovery — the verifier in network/devtransport.py guarantees
    the preconditions row by row; rows that fail take the scalar twin).

    Returns (snd_una', bytes_acked', cwnd', w_max', epoch_start').
    dup_acks/rto_backoff/retries reset to (0, 1, 0) on every advance —
    constants, applied by the caller during writeback."""
    newly = cum_ack - snd_una
    cwnd2, w_max2, eps2 = cc_on_ack(
        cc_id, cwnd, ssthresh, w_max, epoch_start, newly, now, xp=xp)
    return cum_ack, bytes_acked + newly, cwnd2, w_max2, eps2


def rto_min_scan(deadline, xp=np):
    """Vectorized RTO expiry scan: (earliest deadline, its column index)
    over a cohort's armed-RTO deadline column (T_NEVER-filled when
    unarmed).  One min-reduce instead of a heap walk — the device surface
    for timer-wheel-free expiry checks at cohort scale."""
    i = int(xp.argmin(deadline))
    return int(deadline[i]), i


# -- device dispatch ---------------------------------------------------------

#: cohort sizes pad up to the next bucket so every device round reuses
#: one of a handful of compiled program shapes (the devroute pinned-shape
#: discipline: no mid-run compiles)
_BUCKETS = (256, 1024, 4096, 16384, 65536)


class DeviceAckKernel:
    """jax.jit'd ack_advance at pinned bucket shapes.  Results are
    bit-identical to the numpy twin (same integer ops, x64 enabled), so
    routing between them is pure wall-clock policy — the devroute
    argument, applied to transport arithmetic.

    attach() raises when the device path cannot be brought up; the
    caller reports it (network/devtransport.py), it never falls back."""

    def __init__(self, jax, jnp) -> None:
        self._jax = jax
        self._jnp = jnp
        self._fns: dict = {}

    @classmethod
    def attach(cls):
        from shadow_tpu.ops.jaxcfg import configure

        configure()
        import jax
        import jax.numpy as jnp

        jax.config.update("jax_enable_x64", True)
        k = cls(jax, jnp)
        k.run(*[np.zeros(2, dtype=np.int64) for _ in range(8)])
        return k

    def _fn(self, n: int):
        fn = self._fns.get(n)
        if fn is None:
            jnp = self._jnp
            fn = self._jax.jit(
                lambda *cols: ack_advance(*cols, xp=jnp))
            self._fns[n] = fn
        return fn

    def run(self, cc_id, cwnd, ssthresh, w_max, epoch_start, snd_una,
            bytes_acked, cum_ack, now=None):
        """Pad the cohort to a pinned bucket, dispatch, slice the
        readback.  Cohorts above the largest bucket CHUNK at it (rows
        are independent, so chunk boundaries cannot change results)
        instead of compiling a fresh shape mid-run — the devroute
        no-mid-run-compiles discipline.  ``now`` defaults allowed only
        in the warmup call."""
        if now is None:
            now = np.zeros_like(cc_id)
        n = len(cc_id)
        cols = (cc_id, cwnd, ssthresh, w_max, epoch_start, snd_una,
                bytes_acked, cum_ack, now)
        top = _BUCKETS[-1]
        if n > top:
            parts = [self.run(*(c[i:i + top] for c in cols[:8]),
                              now=cols[8][i:i + top])
                     for i in range(0, n, top)]
            return tuple(np.concatenate(ps) for ps in zip(*parts))
        b = next(b for b in _BUCKETS if b >= n)
        if b != n:
            pad = b - n
            # padding rows are inert NewReno slow-start no-ops (newly=0)
            fill = (0, MIN_CWND, 1 << 62, 0, 0, 0, 0, 0, 0)
            cols = tuple(
                np.concatenate([c, np.full(pad, f, dtype=np.int64)])
                for c, f in zip(cols, fill))
        out = self._fn(b)(*cols)
        return tuple(np.asarray(o[:n]) for o in out)
