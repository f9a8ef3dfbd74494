"""TinyML firmware: each node classifies fresh samples with one of the
paper's Tab. 10 ANNs, in an endless loop.

Node ``i`` runs layer configuration ``layers[i % len(layers)]`` (the
configuration file lists them), with that configuration's weights drawn
from ``--seed`` and embedded in the code frame, as the paper's Ex. 2 and
``benchmarks/bench_ann.py`` do.  Each inference refreshes ``input`` from
the node's LCG, runs ``vecfold`` / ``vecadd`` / ``vecmap`` (sigmoid) per
layer, sums the output layer with ``dotprod`` into a checksum, commits
``[inferences, checksum]`` into the DIOS array ``stc`` with one
``vecload``, and prints the ``vecmax`` class.

``reference`` computes the same inferences in numpy and does not import
``repro``: 32-bit wrapping integer sums, truncating scale division and
the paper's Alg. 2 sigmoid over the LUTs its Alg. 3 builds.
``control=True`` uses the float sigmoid, rounded, instead.
"""

from __future__ import annotations

import math

import numpy as np

WORK = "inferences"
STATE = "stc"
LCG_A, LCG_C = 1103515245, 12345
SCALE = -4                 # every layer's scale vector (divide by 4)


def _wrap(a):
    return ((np.asarray(a, np.int64) + (1 << 31)) % (1 << 32)) - (1 << 31)


def _truncdiv(a, b: int):
    q = np.abs(a) // b
    return np.where(a < 0, -q, q)


def weights(layers: list[int], seed: int, index: int):
    """Per layer ``(w (n_in, n_out), b (n_out,))`` drawn from the seed."""
    rng = np.random.default_rng([seed, index])
    out = []
    for n_in, n_out in zip(layers[:-1], layers[1:]):
        w = rng.integers(-20, 20, (n_in, n_out))
        b = rng.integers(-10, 10, n_out)
        out.append((w, b))
    return out


def firmware(layers: list[int], seed: int, index: int) -> str:
    n0 = layers[0]
    lines = ["var x", f"array input {n0}"]
    body = []
    prev = "input"
    for li, (w, b) in enumerate(weights(layers, seed, index), 1):
        n_out = b.shape[0]
        lines.append(f"array w{li} {{ {' '.join(map(str, w.ravel()))} }}")
        lines.append(f"array b{li} {{ {' '.join(map(str, b))} }}")
        lines.append(f"array s{li} {{ {' '.join([str(SCALE)] * n_out)} }}")
        lines.append(f"array a{li} {n_out}")
        body.append(f"  {prev} w{li} a{li} s{li} vecfold")
        body.append(f"  a{li} b{li} a{li} 0 vecadd")
        body.append(f"  a{li} a{li} 0 0 vecmap")
        prev = f"a{li}"
    lines.append(f"array ones {{ {' '.join(['1'] * layers[-1])} }}")
    lines.append("array acc 2")
    lines.append(
        f": refresh {n0} 0 do x @ {LCG_A} * {LCG_C} + dup x ! "
        "16 rshift 1023 and 512 - i input put loop ;"
    )
    lines += [": forward", *body, ";"]
    lines += [
        ": infer refresh forward",
        f"  {prev} ones dotprod 1 acc get + 1 acc put",
        "  0 acc get 1+ 0 acc put",
        "  acc 0 stc vecload",
        f"  {prev} vecmax . ;",
        "0 id get x !",
        "begin infer again",
    ]
    return "\n".join(lines)


def firmwares(params: dict, seed: int) -> list[str]:
    return [firmware(l, seed, c) for c, l in enumerate(params["layers"])]


def seeds(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 1 << 20])
    return rng.integers(-(1 << 31), 1 << 31, n, dtype=np.int64)


def node_data(params: dict, seed: int, n: int):
    fw = np.arange(n) % len(params["layers"])
    return fw, {"id": seeds(seed, n)[:, None], STATE: np.zeros((n, 2), np.int64)}


# --- paper Alg. 2 / Alg. 3 fixed-point sigmoid (x and y scale 1:1000) --------

_LOG10 = np.array([int(math.log10(v / 10.0) * 100.0) for v in range(10, 100)])


def _log10(v):
    """Alg. 2 fplog10 of integers >= 10: x scale 1:10, y scale 1:100."""
    v = np.asarray(v, np.int64)
    shift = np.zeros_like(v)
    for _ in range(9):
        big = v >= 100
        shift += big
        v = np.where(big, v // 10, v)
    return shift * 100 + _LOG10[v - 10]


def _segment_luts():
    """Alg. 3: each segment's LUT, indexed through fplog10 itself."""
    luts = []
    for lo, hi, step, div, den, off, base in (
        (1.0, 2.95, 0.05, 5, 2, 65, 731), (3.0, 9.9, 0.1, 10, 10, 14, 952),
    ):
        tab = {}
        x = lo
        while x <= hi + 1e-9:
            i = int(_log10(int(x * 1000 / div))) // den - off
            tab.setdefault(i, int(1000.0 / (1.0 + math.exp(-x))) - base)
            x += step
        lut = np.zeros(max(tab) + 1, np.int64)
        for i, v in tab.items():
            lut[i] = v
        luts.append(lut)
    return luts


_LUT13, _LUT310 = _segment_luts()


def sigmoid(x):
    x = np.asarray(x, np.int64)
    a = np.abs(x)
    lin = 500 + (a * 231) // 1000
    i13 = _log10(np.maximum(a // 5, 10)) // 2 - 65
    i310 = _log10(np.maximum(a // 10, 10)) // 10 - 14
    seg13 = _LUT13[np.clip(i13, 0, len(_LUT13) - 1)] + 731
    seg310 = _LUT310[np.clip(i310, 0, len(_LUT310) - 1)] + 952
    y = np.where(a <= 1000, lin, np.where(a < 3000, seg13, seg310))
    y = np.where(a >= 10000, 1000, y)
    return np.where(x < 0, 1000 - y, y)


def _float_sigmoid(x):
    return np.round(1000.0 / (1.0 + np.exp(-np.asarray(x) / 1000.0))).astype(np.int64)


def reference(params: dict, seed: int, n: int, count: int,
              control: bool = False):
    """Printed classes ``(n, count)`` and checksums ``(n, count + 1)``."""
    layers = params["layers"]
    act = _float_sigmoid if control else sigmoid
    fw = np.arange(n) % len(layers)
    x0 = seeds(seed, n)
    printed = np.zeros((n, count), np.int64)
    sums = np.zeros((n, count), np.int64)
    for c, lay in enumerate(layers):
        rows = np.flatnonzero(fw == c)
        x = x0[rows]
        ws = weights(lay, seed, c)
        for t in range(count):
            a = np.zeros((rows.size, lay[0]), np.int64)
            for k in range(lay[0]):
                x = _wrap(x * LCG_A + LCG_C)
                a[:, k] = ((x >> 16) & 1023) - 512
            for w, b in ws:
                z = _truncdiv(_wrap(a @ w), -SCALE)
                a = act(_wrap(z + b))
            printed[rows, t] = np.argmax(a, axis=1)
            sums[rows, t] = a.sum(axis=1)
    checksums = np.zeros((n, count + 1), np.int64)
    checksums[:, 1:] = _wrap(np.cumsum(sums, axis=1))
    return printed, checksums
