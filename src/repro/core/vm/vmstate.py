"""Device-resident VM state (a pytree) and host<->device conversion.

The whole machine — code segment, stacks, task table, event table, output
ring, inter-node mailbox — is one NamedTuple of arrays, so it can be jitted
over, vmapped into a parallel-VM ensemble (paper §3.4), stacked along a
leading node axis into a device-resident fleet (``repro.core.vm.fleet``) and
checkpointed/restored byte-exactly (paper resilience feature 5: stop-and-go
processing).

The ``mbox``/``mbox_rd``/``mbox_wr`` fields are the per-node mailbox ring
for fleet ``send``/``receive`` routing: ``mbox`` holds ``[src, value]``
pairs, the counters are monotonic (slot = counter % mbox_size).  A single
host-looped REXAVM leaves them untouched (messages go through the host
queues instead).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.config import VMConfig
from repro.core.vm.spec import NUM_EXC, ST_FREE, ST_YIELD


class VMState(NamedTuple):
    # memories
    cs: jnp.ndarray          # (CS,)  int32 code segment (bytecode + frame data)
    mem: jnp.ndarray         # (MEM,) int32 DIOS data memory (sample buffers...)
    # per-task stacks (paper Alg. 6: DS[STACKSIZE*MAXTASKS])
    ds: jnp.ndarray          # (T, DS) int32
    rs: jnp.ndarray          # (T, RS) int32
    fs: jnp.ndarray          # (T, FS) int32
    dsp: jnp.ndarray         # (T,) int32
    rsp: jnp.ndarray         # (T,) int32
    fsp: jnp.ndarray         # (T,) int32
    # per-task control
    pc: jnp.ndarray          # (T,) int32
    tstatus: jnp.ndarray     # (T,) int32 ST_*
    prio: jnp.ndarray        # (T,) int32
    deadline: jnp.ndarray    # (T,) int32
    timeout: jnp.ndarray     # (T,) int32 wake time (virtual ms)
    ev_addr: jnp.ndarray     # (T,) int32 awaited variable address
    ev_val: jnp.ndarray      # (T,) int32 awaited value
    catch_pc: jnp.ndarray    # (T,) int32 exception catch point
    catch_rsp: jnp.ndarray   # (T,) int32
    pending_exc: jnp.ndarray # (T,) int32 raised, not yet dispatched
    last_exc: jnp.ndarray    # (T,) int32 dispatched, readable by `catch`
    io_op: jnp.ndarray       # (T,) int32 pending FIOS opcode (0 = none)
    # global
    handlers: jnp.ndarray    # (NUM_EXC,) int32 exception handler addresses
    cur: jnp.ndarray         # () int32 current task
    now: jnp.ndarray         # () int32 virtual time in ms
    steps: jnp.ndarray       # () int32 executed instruction count (profiling)
    rng: jnp.ndarray         # () uint32 LCG state
    out: jnp.ndarray         # (OUT*2,) int32 output ring: [kind, value] pairs
    outp: jnp.ndarray        # () int32 entries written (pairs)
    # inter-node mailbox ring (fleet send/receive routing, paper §3.4 networks)
    mbox: jnp.ndarray        # (MBOX*2,) int32 mailbox ring: [src, value] pairs
    mbox_rd: jnp.ndarray     # () int32 messages consumed (monotonic)
    mbox_wr: jnp.ndarray     # () int32 messages delivered (monotonic)


def init_state(cfg: VMConfig, seed: int = 1) -> VMState:
    """A fresh, mutable host (numpy) machine.  Built without touching a
    device: one REXAVM per node, and a device round trip per field adds up
    to minutes at chip-scale fleets."""
    T = cfg.max_tasks
    return VMState(
        cs=np.zeros(cfg.cs_size, np.int32),
        mem=np.zeros(cfg.mem_size, np.int32),
        ds=np.zeros((T, cfg.ds_size), np.int32),
        rs=np.zeros((T, cfg.rs_size), np.int32),
        fs=np.zeros((T, cfg.fs_size), np.int32),
        dsp=np.zeros(T, np.int32),
        rsp=np.zeros(T, np.int32),
        fsp=np.zeros(T, np.int32),
        pc=np.zeros(T, np.int32),
        tstatus=np.full(T, ST_FREE, np.int32),
        prio=np.zeros(T, np.int32),
        deadline=np.zeros(T, np.int32),
        timeout=np.zeros(T, np.int32),
        ev_addr=np.zeros(T, np.int32),
        ev_val=np.zeros(T, np.int32),
        catch_pc=np.zeros(T, np.int32),
        catch_rsp=np.zeros(T, np.int32),
        pending_exc=np.zeros(T, np.int32),
        last_exc=np.zeros(T, np.int32),
        io_op=np.zeros(T, np.int32),
        handlers=np.zeros(NUM_EXC, np.int32),
        cur=np.array(0, np.int32),
        now=np.array(0, np.int32),
        steps=np.array(0, np.int32),
        rng=np.array(seed, np.uint32),
        out=np.zeros(cfg.out_ring_size * 2, np.int32),
        outp=np.array(0, np.int32),
        mbox=np.zeros(cfg.mbox_size * 2, np.int32),
        mbox_rd=np.array(0, np.int32),
        mbox_wr=np.array(0, np.int32),
    )


def to_numpy(st: VMState) -> VMState:
    """Mutable host copy (np.asarray views of jax arrays are read-only)."""
    return VMState(*[np.array(x) for x in st])


def to_device(st: VMState) -> VMState:
    return VMState(*[jnp.asarray(x) for x in st])


def state_nbytes(st: VMState) -> int:
    """Total byte size of one state (or one stacked fleet state)."""
    return sum(int(x.nbytes) for x in st)


def stack_states(states: list[VMState]) -> VMState:
    """Stack per-node states along a new leading node axis (host side)."""
    return VMState(
        *[
            np.stack([np.asarray(getattr(s, f)) for s in states])
            for f in VMState._fields
        ]
    )


def stack1(x) -> jnp.ndarray:
    """One-node stack: a host field -> device array with a leading node
    axis (the single-VM view of the batched executors)."""
    return jnp.asarray(np.asarray(x))[None]


def take_nodes(S: VMState, idx) -> VMState:
    """Gather node slices ``idx`` from a stacked fleet state (device op:
    under a node-sharded state this lowers to a cross-shard gather)."""
    idx = jnp.asarray(idx)
    return VMState(*[x[idx] for x in S])


def put_nodes(S: VMState, idx, sub: VMState) -> VMState:
    """Scatter node slices ``sub`` back into a stacked fleet state at rows
    ``idx`` (the partial-IO write-back collective)."""
    idx = jnp.asarray(idx)
    return VMState(
        *[x.at[idx].set(jnp.asarray(u)) for x, u in zip(S, sub)]
    )


def launch_task(st: VMState, task: int, entry: int, prio: int = 0, deadline: int = 0) -> VMState:
    """Host-side: point task slot ``task`` at ``entry`` and mark it ready."""
    st = to_numpy(st)
    st.pc[task] = entry
    st.dsp[task] = 0
    st.rsp[task] = 0
    st.fsp[task] = 0
    st.tstatus[task] = ST_YIELD
    st.prio[task] = prio
    st.deadline[task] = deadline
    st.catch_pc[task] = 0       # cell 0 holds a canonical `end`
    st.catch_rsp[task] = 0
    st.pending_exc[task] = 0
    st.last_exc[task] = 0
    st.io_op[task] = 0
    return st


# Output ring entry kinds.
OUT_NUM = 0
OUT_CHR = 1


def decode_output(st: VMState) -> str:
    """Render the output ring as text (host side)."""
    out = np.asarray(st.out)
    n = int(st.outp)
    parts: list[str] = []
    for k in range(n):
        kind, val = int(out[2 * k]), int(out[2 * k + 1])
        if kind == OUT_CHR:
            parts.append(chr(val & 0xFF))
        else:
            parts.append(f"{val} ")
    return "".join(parts)


def clear_output(st: VMState) -> VMState:
    return st._replace(out=jnp.zeros_like(st.out), outp=jnp.int32(0))
