"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel directory has:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, reshaping, interpret switch)
  ref.py    — pure-jnp oracle used by the allclose sweeps in tests/

Subsystems:
  fixmatmul  — int8 fixed-point matmul (paper C4)
  flashattn  — flash attention
  lutact     — LUT fixed-point sigmoid (paper Alg. 2, C5)
  rwkv6_scan — RWKV6 chunked WKV scan
  vmloop     — the VM fleet's inner interpreter loop: an on-chip
               fetch/dispatch/stack engine (one grid program per node,
               the node's machine state DMA'd into scalar memory, a
               branch table over the opcode space), byte-exact vs the
               lax interpreter/Oracle over its claimed opcode set and
               bailing to the lax tail otherwise.
               Selected per fleet via ``FleetVM(executor="pallas")`` /
               ``REXAVM(backend="pallas")`` rather than ``use_kernels()``.

On a CPU backend kernels run under interpret=True (tests); on a TPU they
run compiled and interpret mode is refused (``check_interpret``).  Models
select the kernel vs jnp path via ``repro.kernels.use_kernels()``.
"""

import jax

_FORCE = None  # None = auto (TPU only), True/False = override


def set_kernels(mode):
    """mode: 'auto' | 'on' | 'off' | 'interpret'."""
    global _FORCE
    _FORCE = {"auto": None, "on": True, "off": False, "interpret": "interpret"}[mode]


def use_kernels():
    """True when Pallas kernels should run compiled (TPU, or forced 'on')."""
    if _FORCE is True:
        return True
    if _FORCE in (False, "interpret"):
        return False
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """True only when kernels are forced into interpret mode (CPU testing)."""
    return check_interpret(_FORCE == "interpret")


def check_interpret(interpret: bool) -> bool:
    """Pass ``interpret`` through, refusing it on a TPU backend: there the
    kernels run compiled, never in the Pallas interpreter."""
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend; kernels run "
            "compiled there (set_kernels('off'/'interpret') is for CPU tests)"
        )
    return interpret
