"""On-device mailbox routing for the VM fleet — the collective layer.

PR 1 routed ``send``/``receive`` with a ``lax.fori_loop`` over all (node,
task) pairs, one dynamic scatter per pair.  That formulation is sequential on
device and — worse — assumes the whole node axis is one local array, so it
cannot be partitioned.  This module restates the exact same round semantics
as a handful of *vectorized* gathers/scatters over the node axis:

  * **send phase** — every pending ``send`` is described by a flat
    ``(valid, dst, value)`` descriptor in (node, task) order.  Delivery order
    and backpressure are resolved by a *rank*: send ``k`` to destination
    ``d`` is delivered iff fewer than ``space(d)`` valid sends to ``d``
    precede it.  Ranks come from one stable destination-major sort of the
    flat descriptors (a segmented rank, O(NT log NT) — no quadratic
    incidence matrix), after which all deliveries land in one
    collision-free scatter into the stacked mailbox rings (each delivery
    owns a distinct ``(dst, slot)`` pair).  Under a ``NamedSharding`` over
    the ``"node"`` mesh axis, XLA's SPMD partitioner turns the descriptor
    broadcast into an all-gather and the mailbox write into a cross-shard
    scatter — the mailbox exchange *is* the collective.
  * **receive phase** — purely node-local: each node pops its own ring, one
    task per sweep in ascending task order (``T`` static sweeps).  No
    cross-shard traffic at all, matching rBPF's "per-node VM state stays
    tiny and local" argument.

Semantics are byte-for-byte those of :func:`repro.core.vm.fleet.reference_round`
(all sends in (node, task) order, then all receives; full mailbox =>
backpressure, out-of-range destination => drop): tests/test_vm_fleet.py and
the randomized program tests assert exact state equality.

Under the Pallas executor's *message-bound round mode*
(``FleetVM.run(service_every=k)`` with ``executor="pallas"``), this router
runs **between kernel invocations** inside one compiled
``FleetKernels.rounds_aux`` loop: the vmloop kernel executes each
``send``/``receive`` suspension in-kernel (pc rewind + ``io_op`` +
ST_IOWAIT), and the collective here delivers/resumes — so a message-bound
ring ping-pongs kernel <-> router for ``k`` whole rounds per host probe
without ever reaching the lax tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import VMConfig
from repro.core.vm.spec import ISA, ST_IOWAIT, ST_YIELD, get_isa
from repro.core.vm.vmstate import VMState

I32 = jnp.int32


def build_router(cfg: VMConfig, isa: ISA | None = None, obs: bool = False):
    """Returns ``route(S) -> (S, progress)`` over a stacked fleet ``VMState``.

    ``progress[i]`` is True when any of node ``i``'s tasks was resumed this
    round — the per-node analogue of ``REXAVM._service_io``'s return value,
    consumed by the fleet round's virtual-time warp.

    With ``obs=True`` the router returns ``(S, progress, (drops, depth))``:
    ``drops`` is the number of messages dropped this round (sends to an
    out-of-range destination), ``depth`` the mailbox high-watermark — the
    deepest ring occupancy on any node right after the send phase (before
    receives pop), i.e. the round's peak queueing pressure.
    """
    isa = isa or get_isa()
    T = cfg.max_tasks
    DS = cfg.ds_size
    MB = cfg.mbox_size
    OP_SEND = isa.opcode["send"]
    OP_RECV = isa.opcode["receive"]

    def send_phase(S: VMState):
        """All sends, (node, task) order, one collective gather/scatter."""
        N = S.pc.shape[0]
        is_send = (S.tstatus == ST_IOWAIT) & (S.io_op == OP_SEND)     # (N, T)
        # send ( v dst -- ): dst on top, both still on DS (pc rewound).
        dst = jnp.take_along_axis(
            S.ds, jnp.clip(S.dsp - 1, 0, DS - 1)[..., None], axis=2
        )[..., 0]
        val = jnp.take_along_axis(
            S.ds, jnp.clip(S.dsp - 2, 0, DS - 1)[..., None], axis=2
        )[..., 0]
        dst_ok = (dst >= 0) & (dst < N)
        dstc = jnp.clip(dst, 0, N - 1)
        valid = is_send & dst_ok

        # Flat (node, task) order k = i*T + t — the reference's sequential
        # processing order, which fixes both ring content and backpressure.
        vf = valid.reshape(-1)                                        # (N*T,)
        df = dstc.reshape(-1)
        NT = N * T
        # rank[k] = number of valid sends to the same destination before k.
        # Segmented rank via one stable sort (O(NT log NT), no (NT, N)
        # incidence matrix): group valid sends by destination — invalid
        # entries sort to the tail — keep k-order within each group, then
        # rank = position - segment start.  The sort key is the destination
        # alone (stability keeps k-order): a (dst, k) composite overflows
        # int32 once N * N * T reaches 2^31 (16,384 nodes of 8 tasks).
        k = jnp.arange(NT, dtype=I32)
        order = jnp.argsort(jnp.where(vf, df, N), stable=True)
        pos = jnp.arange(NT, dtype=I32)
        sd = df[order]
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), sd[1:] != sd[:-1]]
        )
        seg_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
        rank = jnp.zeros(NT, I32).at[order].set(pos - seg_start)
        # space0 never grows during the phase (receives run strictly after),
        # so "delivered" == "rank below the initial free space".
        space0 = jnp.maximum(MB - (S.mbox_wr - S.mbox_rd), 0)         # (N,)
        deliver = vf & (rank < space0[df])
        # Full mailbox => backpressure (sender retries next round);
        # invalid destination => message dropped, sender resumes.
        resume = is_send & ((~dst_ok) | deliver.reshape(N, T))

        # Every delivery owns a distinct (dst, slot): one-shot scatter.
        slot = (S.mbox_wr[df] + rank) % MB
        row = jnp.where(deliver, df, N)                # N = dropped scatter
        src = k // T
        mbox = S.mbox.at[row, 2 * slot].set(src, mode="drop")
        mbox = mbox.at[row, 2 * slot + 1].set(val.reshape(-1), mode="drop")
        sends_to = jnp.zeros((N,), I32).at[df].add(vf.astype(I32))
        delivered_to = jnp.minimum(sends_to, space0)

        S = S._replace(
            mbox=mbox,
            mbox_wr=S.mbox_wr + delivered_to,
            dsp=jnp.where(resume, S.dsp - 2, S.dsp),
            pc=jnp.where(resume, S.pc + 1, S.pc),
            io_op=jnp.where(resume, I32(0), S.io_op),
            tstatus=jnp.where(resume, I32(ST_YIELD), S.tstatus),
        )
        drops = (is_send & ~dst_ok).sum().astype(I32)
        return S, resume.any(axis=1), drops

    def recv_phase(S: VMState):
        """All receives: node-local ring pops, tasks in ascending order."""
        N = S.pc.shape[0]
        nodes = jnp.arange(N)
        progress = jnp.zeros((N,), bool)
        for t in range(T):                       # static sweep, T is small
            is_recv = (S.tstatus[:, t] == ST_IOWAIT) & (
                S.io_op[:, t] == OP_RECV
            )
            deliver = is_recv & (S.mbox_wr > S.mbox_rd)
            slot = S.mbox_rd % MB
            src = jnp.take_along_axis(S.mbox, (2 * slot)[:, None], axis=1)[:, 0]
            v = jnp.take_along_axis(
                S.mbox, (2 * slot + 1)[:, None], axis=1
            )[:, 0]
            row = jnp.where(deliver, nodes, N)
            dsp = S.dsp[:, t]
            # receive ( -- src v ): push src, then the value.
            ds = S.ds.at[row, t, jnp.clip(dsp, 0, DS - 1)].set(
                src, mode="drop"
            )
            ds = ds.at[row, t, jnp.clip(dsp + 1, 0, DS - 1)].set(
                v, mode="drop"
            )
            S = S._replace(
                ds=ds,
                dsp=S.dsp.at[row, t].add(2, mode="drop"),
                mbox_rd=S.mbox_rd.at[row].add(1, mode="drop"),
                pc=S.pc.at[row, t].add(1, mode="drop"),
                io_op=S.io_op.at[row, t].set(0, mode="drop"),
                tstatus=S.tstatus.at[row, t].set(ST_YIELD, mode="drop"),
            )
            progress = progress | deliver
        return S, progress

    def route(S: VMState):
        S, sent, _ = send_phase(S)
        S, received = recv_phase(S)
        return S, sent | received

    def route_obs(S: VMState):
        S, sent, drops = send_phase(S)
        depth = jnp.max(S.mbox_wr - S.mbox_rd).astype(I32)
        S, received = recv_phase(S)
        return S, sent | received, (drops, depth)

    return route_obs if obs else route
