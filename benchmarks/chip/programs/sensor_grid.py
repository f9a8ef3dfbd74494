"""Sensor-grid firmware: one endless sampling loop per node of a torus.

Each sampling period a node draws a sample from its own LCG, keeps the
last ``window`` samples in a mirrored ring, low-pass filters that window
with ``lowp`` and takes the newest filtered value ``f``.  It reports to
its 4 torus neighbours on delta (send-on-delta): when ``f`` has moved by
more than the node's threshold since the last event, it sends a burst of
``burst`` messages to each neighbour (``f`` and its newest raw samples);
otherwise one message (``f``).  Thresholds are skewed over the nodes, so
a few nodes burst most periods and most rarely do (the traffic mix gives
the thresholds and their shares).  Sends are data-dependent, so the
fleet drifts out of lockstep and mailboxes hold backlog.

Each message carries a "more follows" bit.  A node finishes its period
when every neighbour's burst for that period is complete; a message from
a neighbour whose burst is already complete belongs to that neighbour's
next period and counts there.  A neighbour can be at most one period
ahead, so a mailbox holds at most ``2 * 4 * burst`` messages.  The node
then commits ``[periods, checksum]`` (the checksum sums every value of
every burst received) into the DIOS array ``stc`` with one ``vecload``,
so the host never reads a half-updated pair, and prints ``f``.

``reference`` computes the same periods in numpy and does not import
``repro``: 32-bit wrapping integer arithmetic, truncating division, the
IIR recursion of the ``lowp`` word, the events and bursts.  What a node
receives per period does not depend on timing, so the reference needs
no model of rounds.  ``control=True`` runs the filter in float32 instead
of fixed point.
"""

from __future__ import annotations

import numpy as np

WORK = "periods"          # what one committed count of ``stc[0]`` is
STATE = "stc"             # DIOS array the firmware commits into
LCG_A, LCG_C = 1103515245, 12345
BIAS = 4096               # added to a value before it is encoded


def _wrap(a):
    """Two's-complement 32-bit wrap of an int64 array."""
    return ((np.asarray(a, np.int64) + (1 << 31)) % (1 << 32)) - (1 << 31)


def _truncdiv(a, b: int):
    q = np.abs(a) // b
    return np.where(a < 0, -q, q)


def neighbours(side: int) -> np.ndarray:
    """(n, 4) fleet indices of each node's right, down, left, up."""
    i = np.arange(side * side)
    r, c = i // side, i % side
    return np.stack([
        r * side + (c + 1) % side,
        ((r + 1) % side) * side + c,
        r * side + (c - 1) % side,
        ((r - 1) % side) * side + c,
    ], axis=1)


def _check(params: dict) -> None:
    w, b, mbox = params["window"], params["burst"], params["mbox_size"]
    if w & (w - 1):
        raise ValueError(f"the sample window {w} is not a power of two")
    if not 1 <= b <= w or 8 * b > mbox:
        raise ValueError(f"a burst of {b} does not fit the window {w} and "
                         f"mailboxes of {mbox} (at most 2 bursts x 4 "
                         f"neighbours are queued)")


def firmwares(params: dict, seed: int) -> list[str]:
    """The one firmware every node runs (identity comes from DIOS ``id``:
    4 neighbours, LCG seed, event threshold)."""
    _check(params)
    w, k, b = params["window"], params["pole_k"], params["burst"]
    extra = "\n".join(
        f"  pos @ {w - 1 - j} + hist get {1 if j < b - 2 else 0} tx"
        for j in range(b - 1)
    )
    return [f"""
var x var pos var ref var cur var nxt
array hist {2 * w}
array buf {w}
array acc 2
array done 4
array ndone 4
: sample x @ {LCG_A} * {LCG_C} + dup x ! 16 rshift 1023 and 512 - ;
: tx swap {BIAS} + 2* +
  dup 0 id get send dup 1 id get send dup 2 id get send 3 id get send ;
: dir dup 1 id get = 1 and over 2 id get = 2 and or
  over 3 id get = 3 and or nip ;
: take swap dir over 2/ {BIAS} -
  over done get if
    nxt +! swap 1 and 0= if 1 swap ndone put else drop then
  else
    cur +! swap 1 and 0= if 1 swap done put else drop then
  then ;
: alldone 0 done get 1 done get and 2 done get and 3 done get and ;
: gather begin alldone 0= while receive take repeat ;
: roll
  cur @ 1 acc get + 1 acc put nxt @ cur ! 0 nxt !
  4 0 do i ndone get i done put 0 i ndone put loop ;
: event
  dup ref ! dup {1 if b > 1 else 0} tx
{extra}
  ;
: period
  sample dup pos @ hist put pos @ {w} + hist put
  pos @ 1+ {w - 1} and pos !
  hist pos @ buf vecload
  buf 0 {w} {k} lowp
  {w - 1} buf get
  dup ref @ - abs 5 id get > if event else dup 0 tx then
  gather roll
  0 acc get 1+ 0 acc put
  acc 0 stc vecload
  . ;
4 id get x !
begin period again
"""]


def seeds(seed: int, n: int) -> np.ndarray:
    """Each node's LCG seed, drawn from ``--seed``."""
    rng = np.random.default_rng(seed)
    return rng.integers(-(1 << 31), 1 << 31, n, dtype=np.int64)


def thresholds(params: dict, seed: int, n: int) -> np.ndarray:
    """Each node's event threshold: the traffic's ``[threshold, share]``
    pairs repeated over the nodes in proportion to their shares, then
    shuffled by ``--seed``.  Every seed gets the same multiset."""
    pattern = np.repeat([d for d, _ in params["thresholds"]],
                        [s for _, s in params["thresholds"]])
    per = np.resize(pattern, n).astype(np.int64)
    return np.random.default_rng([seed, 1]).permutation(per)


def node_data(params: dict, seed: int, n: int):
    """Per node: which firmware it runs and its DIOS arrays, in order."""
    side = params["side"]
    if side * side != n or side < 3:
        raise ValueError(f"a {side} x {side} torus does not have {n} nodes")
    ident = np.concatenate([neighbours(side), seeds(seed, n)[:, None],
                            thresholds(params, seed, n)[:, None]], axis=1)
    return np.zeros(n, np.int64), {"id": ident, STATE: np.zeros((n, 2), np.int64)}


def _lowp(vals: np.ndarray, k: int, control: bool) -> np.ndarray:
    """Last output of the in-place IIR low-pass over each row."""
    if control:
        v = vals.astype(np.float32)
        y = v[:, 0]
        for i in range(v.shape[1]):
            y = y + np.float32(k) * (v[:, i] - y) / np.float32(1000)
        return np.round(y).astype(np.int64)
    y = vals[:, 0].astype(np.int64)
    for i in range(vals.shape[1]):
        y = _wrap(y + _truncdiv(_wrap(k * (vals[:, i] - y)), 1000))
    return y


def reference(params: dict, seed: int, n: int, periods: int,
              control: bool = False):
    """Printed values ``(n, periods)`` and checksums ``(n, periods + 1)``
    (``checksums[:, p]`` is the committed sum after ``p`` periods)."""
    w, k, side, b = (params["window"], params["pole_k"], params["side"],
                     params["burst"])
    x = seeds(seed, n)
    delta = thresholds(params, seed, n)
    hist = np.zeros((n, 2 * w), np.int64)
    ref = np.zeros(n, np.int64)
    pos = 0
    printed = np.zeros((n, periods), np.int64)
    sent = np.zeros((n, periods), np.int64)       # sum of each burst
    for p in range(periods):
        x = _wrap(x * LCG_A + LCG_C)
        s = ((x >> 16) & 1023) - 512
        hist[:, pos] = s
        hist[:, pos + w] = s
        pos = (pos + 1) & (w - 1)
        f = _lowp(hist[:, pos:pos + w], k, control)
        event = np.abs(f - ref) > delta
        ref = np.where(event, f, ref)
        raw = hist[:, pos + w - b + 1:pos + w].sum(axis=1)
        sent[:, p] = f + np.where(event, raw, 0)
        printed[:, p] = f
    nsum = sent[neighbours(side)].sum(axis=1)            # (n, periods)
    checksums = np.zeros((n, periods + 1), np.int64)
    checksums[:, 1:] = _wrap(np.cumsum(nsum, axis=1))
    return printed, checksums
