"""Fleet metrics schema — counters, classification, counting slice engines.

One namespace for everything the fleet can count, with one hard rule: the
schema is executor-independent.  ``FleetVM.metrics()`` returns the same
key set under the batched lax interpreter, the Oracle, the Pallas vmloop
kernel and the trace-JIT — backends that cannot produce a counter report
it as zero, never as a missing key.

The load-bearing definition is the per-opcode retirement **bin**.  Every
retired instruction — and *only* retired instructions — lands in exactly
one of ``num_ops + 4`` bins:

  ``0 .. num_ops-1``   the ISA opcode (tag 0, payload clipped like the
                       interpreter's ``exec_op`` — out-of-range payloads
                       below FIOS alias to the trap slot below);
  ``num_ops``          "fios/trap": tag-0 payload >= num_ops (a FIOS host
                       call's suspension step, or an out-of-table trap);
  ``num_ops + 1``      literal push (tag 1);
  ``num_ops + 2``      call (tag 2);
  ``num_ops + 3``      invalid: reserved tag 3, or an out-of-bounds pc
                       (the invalid-pc trap still bumps ``steps``, so it
                       still must bin somewhere).

Because every engine retires byte-identical instruction sequences (the
repo's equivalence contract), per-bin counts are *comparable across
executors* — tests/test_vm_obs.py asserts exact equality over the full
ISA sweep.  Four counting engines are built here from the interpreter's
own parts (``_schedule``/``_step_instr``), so counting can never diverge
from execution:

  * :func:`make_counting_slice`  — schedule → counting vmloop → preempt
    (the jit/batched engines);
  * :func:`make_counting_finish` — counting vmloop with a *traced* bound +
    preempt (the pallas lax tail and the trace-JIT generic tail);
  * :func:`classify_host`        — the numpy mirror for the Oracle's
    ``step_hook``;
  * :func:`trace_spec_hist`      — closed-form bin counts for a recorded
    trace's specialized steps (prefix sums over the recorded path + its
    loop cycle), so the trace engine counts without re-executing.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import numpy as np

from repro.core.vm.spec import ISA, ST_RUN, ST_YIELD, TAG_OP

EXTRA_BINS = ("fios/trap", "lit", "call", "invalid")


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ObsConfig:
    """Observability switchboard (hashable: joins jit/engine cache keys).

    ``trace``            — keep the fleet's spans in a host ring for
                           ``export_trace()`` and add the round-phase spans
                           (one device sync per phase so their walls are
                           honest);
    ``trace_ring``       — host ring-buffer capacity in span events;
    ``deadline_ms``      — virtual-clock round deadline: a node misses when
                           its per-round clock increment exceeds this many
                           virtual ms (0 disables).  Deterministic and
                           byte-exact across executors;
    ``deadline_wall_ms`` — wall-clock round deadline for the host latency
                           monitor (0 disables);
    ``time_rounds``      — feed the wall-clock latency histogram (one
                           ``block_until_ready`` per round).

    The fleet's spans reach any ``jax.profiler`` trace whether or not
    ``trace`` is set (``repro.obs.tracing``).
    """

    trace: bool = False
    trace_ring: int = 1024
    deadline_ms: int = 0
    deadline_wall_ms: float = 0.0
    time_rounds: bool = True


def normalize_obs(obs) -> ObsConfig | None:
    """``None``/``False`` -> off, ``True`` -> defaults, config passes through."""
    if obs is None or obs is False:
        return None
    if obs is True:
        return ObsConfig()
    if isinstance(obs, ObsConfig):
        return obs
    raise TypeError(
        f"obs must be None, a bool, or an ObsConfig; got {type(obs).__name__}"
    )


# ---------------------------------------------------------------------------
# Retirement bins
# ---------------------------------------------------------------------------

def n_bins(isa: ISA) -> int:
    return isa.num_ops + len(EXTRA_BINS)


def bin_names(isa: ISA) -> list[str]:
    return [isa.name[c] for c in range(isa.num_ops)] + list(EXTRA_BINS)


def hist_to_dict(hist, isa: ISA) -> dict[str, int]:
    """Full-key mapping (zeros included) so schemas compare structurally."""
    h = np.asarray(hist)
    return {name: int(h[i]) for i, name in enumerate(bin_names(isa))}


def classify_host(pc_ok: bool, instr: int, num_ops: int) -> int:
    """Bin of one retired instruction, host side (Oracle ``step_hook``).

    Mirrors the device classifiers bit for bit: python ints share numpy's
    arithmetic-shift / two's-complement ``&`` semantics for the int32
    values the Oracle fetches.
    """
    if not pc_ok:
        return num_ops + 3
    tag = instr & 3
    if tag == TAG_OP:
        return min(max(instr >> 2, 0), num_ops)
    return num_ops + tag


def make_bin_of(cfg, isa: ISA) -> Callable:
    """Device classifier: bin of the instruction a single-node state is
    *about to* retire (fetch-time, before ``step_instr``)."""
    import jax.numpy as jnp

    CS = cfg.cs_size
    num_ops = isa.num_ops

    def bin_of(st):
        t = st.cur
        pc = st.pc[t]
        pc_ok = (pc >= 0) & (pc < CS)
        instr = st.cs[jnp.clip(pc, 0, CS - 1)]
        tag = instr & 3
        payload = (instr >> 2).astype(jnp.int32)
        b = jnp.where(tag == TAG_OP, jnp.clip(payload, 0, num_ops), num_ops + tag)
        return jnp.where(pc_ok, b, num_ops + 3).astype(jnp.int32)

    return bin_of


# ---------------------------------------------------------------------------
# Counting slice engines (built from the interpreter's own parts)
# ---------------------------------------------------------------------------

def make_counting_finish(interp) -> Callable:
    """``(st, remaining) -> (st, hist)``: the lax vmloop with a *traced*
    step bound and per-step bin counting, then the standard preempt —
    byte-identical to ``vmloop_rest + preempt`` / ``finish_one`` with a
    histogram riding the while carry."""
    import jax.numpy as jnp
    from jax import lax

    step_instr = interp._step_instr
    bin_of = make_bin_of(interp.cfg, interp.isa)
    NB = n_bins(interp.isa)

    def finish(st, remaining):
        def cond(carry):
            s, n, h = carry
            return (n < remaining) & (s.tstatus[s.cur] == ST_RUN)

        def body(carry):
            s, n, h = carry
            h = h.at[bin_of(s)].add(1)
            return step_instr(s), n + 1, h

        st, _, hist = lax.while_loop(
            cond, body, (st, jnp.int32(0), jnp.zeros(NB, jnp.int32))
        )
        still = st.tstatus[st.cur] == ST_RUN
        st = lax.cond(
            still,
            lambda s: s._replace(tstatus=s.tstatus.at[s.cur].set(ST_YIELD)),
            lambda s: s,
            st,
        )
        return st, hist

    return finish


def make_counting_slice(interp) -> Callable:
    """``(st, steps) -> (st, found, hist)``: one full micro-slice
    (schedule → counting vmloop → preempt) for the single-node jit path
    and the vmapped batched path."""
    schedule = interp._schedule
    finish = make_counting_finish(interp)

    def slice_obs(st, steps):
        st, found = schedule(st)
        # The counting loop runs unconditionally: an un-woken task never
        # satisfies tstatus[cur] == ST_RUN, so the loop is a no-op for it
        # (the same composition the pallas engine relies on).
        st, hist = finish(st, steps)
        return st, found, hist

    return slice_obs


def trace_spec_hist(n, hp, length: int, loop_start: int):
    """Bin counts of the first ``n`` specialized steps of a recorded path.

    ``hp`` is the trace's ``(TRACE_MAX+1, NB)`` prefix-sum table
    (``hp[k]`` = bins of the first ``k`` recorded positions).  The
    compiled trace fn executes positions ``0..length-1`` then wraps to
    ``loop_start``, so for ``n`` retired steps::

        base  = hp[min(n, length)]
        extra = max(n - length, 0)            # steps past the first pass
        cycle = hp[length] - hp[loop_start]   # one full wrap
        tail  = hp[loop_start + extra % len(cycle)] - hp[loop_start]

    Guards only ever *stop* consumption, so the retired prefix is always
    exactly this position sequence.  ``n`` is a vector (per-node counts);
    returns the summed ``(NB,)`` histogram.
    """
    import jax.numpy as jnp

    n = jnp.asarray(n, jnp.int32)
    hp = jnp.asarray(hp, jnp.int32)
    base = hp[jnp.minimum(n, length)]                       # (M, NB)
    extra = jnp.maximum(n - length, 0)
    cyc_len = max(length - loop_start, 1)
    cycle = (hp[length] - hp[loop_start])[None, :]
    tail = hp[loop_start + extra % cyc_len] - hp[loop_start][None, :]
    return (base + (extra // cyc_len)[:, None] * cycle + tail).sum(axis=0)


# ---------------------------------------------------------------------------
# Per-slice / per-round device aggregates
# ---------------------------------------------------------------------------

class ExecAux(NamedTuple):
    """Per-round execute-phase counters (device scalars/vectors).

    Backends fill what they measure and zero the rest: ``op_hist`` and
    ``io_susp`` are universal (and byte-exact-comparable); ``deopts`` is
    backend-specific (pallas bail-outs / trace guard exits);
    ``kernel_steps``/``bailed``/``bail_hist`` feed ``pallas_stats()``.
    """

    op_hist: Any           # (NB,) int32 — instructions retired per bin
    io_susp: Any           # ()  int32 — tasks newly IO-suspended this slice
    deopts: Any            # ()  int32 — bail-outs / guard exits
    kernel_steps: Any      # ()  int32 — pallas in-kernel retirements
    bailed: Any            # ()  int32 — pallas bailed node-rounds
    bail_hist: Any         # (num_ops+1,) int32 — per-opcode bail counts


def zero_exec_aux(isa: ISA):
    import jax.numpy as jnp

    z = jnp.int32(0)
    return ExecAux(
        op_hist=jnp.zeros(n_bins(isa), jnp.int32),
        io_susp=z,
        deopts=z,
        kernel_steps=z,
        bailed=z,
        bail_hist=jnp.zeros(isa.num_ops + 1, jnp.int32),
    )


class ObsCounters(NamedTuple):
    """The fleet's accumulated on-device counters (a lazy pytree: the
    round loop only ever *adds* to it asynchronously; ``metrics()`` is the
    single sync point)."""

    op_retired: Any        # (NB,) int32
    mbox_high: Any         # ()  int32 — max mailbox depth after any send phase
    mbox_drops: Any        # ()  int32 — messages dropped (invalid destination)
    io_susp: Any           # ()  int32
    deopts: Any            # ()  int32
    deadline_miss: Any     # (N,) int32 — virtual-clock deadline misses per node
    rounds: Any            # ()  int32 — rounds observed


def zero_counters(n: int, isa: ISA) -> ObsCounters:
    import jax.numpy as jnp

    z = jnp.int32(0)
    return ObsCounters(
        op_retired=jnp.zeros(n_bins(isa), jnp.int32),
        mbox_high=z,
        mbox_drops=z,
        io_susp=z,
        deopts=z,
        deadline_miss=jnp.zeros(n, jnp.int32),
        rounds=z,
    )


# ---------------------------------------------------------------------------
# The unified snapshot
# ---------------------------------------------------------------------------

@dataclass
class FleetMetrics:
    """Schema-stable snapshot of one fleet's telemetry.

    Sections (identical key sets under every executor):

    ``executor``  — the active backend name;
    ``rounds``    — fleet rounds driven since construction;
    ``counters``  — the on-device ObsCounters (zeroed when obs is off);
    ``latency``   — the wall-clock round-latency histogram + deadline
                    misses (``DeadlineMonitor.snapshot()``);
    ``pallas``    — ``pallas_stats()`` minus the duplicate executor key;
    ``trace``     — ``trace_stats()`` minus the duplicate executor key;
    ``transfers`` — ``transfer_stats()`` minus executor/rounds;
    ``executive`` — ``executive_stats()`` minus the duplicate executor key
                    (task switches, preemptions, per-task deadline misses,
                    syscall-plane counters; zeroed without an Executive).
    """

    executor: str
    rounds: int
    counters: dict = field(default_factory=dict)
    latency: dict = field(default_factory=dict)
    pallas: dict = field(default_factory=dict)
    trace: dict = field(default_factory=dict)
    transfers: dict = field(default_factory=dict)
    executive: dict = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "executor": self.executor,
            "rounds": self.rounds,
            "counters": self.counters,
            "latency": self.latency,
            "pallas": self.pallas,
            "trace": self.trace,
            "transfers": self.transfers,
            "executive": self.executive,
        }

    def __getitem__(self, key):
        return self.as_dict()[key]

    def keys(self):
        return self.as_dict().keys()
