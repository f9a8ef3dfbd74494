"""Input-Output System (paper §3.6, Def. 2): the VM's foreign interface.

``FiosRegistry``  — host functions bridged into the word set (fiosAdd);
                    now a deprecation shim over the numbered SVC table in
                    ``repro.exec.syscalls`` (stable syscall numbers with
                    declared arities, vectorized batch handlers).
``DiosRegistry``  — host data arrays mapped into the VM address space
                    at ``MEM_BASE`` (diosAdd); e.g. the ADC sample buffer.
``HostLink``      — host-side message bus between REXAVM nodes: wires each
                    node's ``send`` into the destination's ``recv_queue``.
``FleetIOService``— partial-state IO service for the fleet runtime: instead
                    of syncing the *whole* stacked fleet state to the host
                    whenever any node suspends on host IO, it gathers only
                    the suspended nodes' slices (by node index), services
                    them through the ordinary per-node frontends, and
                    scatters the slices back — both movements are node-axis
                    collectives under a mesh-sharded fleet.

Device-side execution of a FIOS word suspends the task (``ST_IOWAIT`` — the
paper's "leaving the current VM interpreter loop round"); the host service
loop pops arguments from the data stack, invokes the callback, pushes the
result, and resumes.  This *is* the paper's nested-execution-loop design
(Fig. 10) and is what makes the interpreter fully jittable.

``send``/``receive`` between nodes have two transports: ``HostLink`` (every
message takes a host round trip — the seed behaviour, kept as the simple
path) and the device-resident mailbox rings of
:class:`repro.core.vm.fleet.FleetVM`, which route whole message rounds
on device without leaving XLA.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import TYPE_CHECKING, Callable, Optional

import numpy as np

from repro.core.vm.spec import FIOS_BASE, MAX_FIOS, MEM_BASE

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.vm.machine import REXAVM


@dataclass
class FiosEntry:
    name: str
    fn: Callable
    args: int           # number of cells popped from DS
    ret: int            # number of cells pushed (0 or 1; 2 for paper doubles)


class FiosRegistry:
    """Deprecated name-keyed facade over the numbered SVC table.

    Host callbacks now live in :class:`repro.exec.syscalls.SyscallTable`
    (stable syscall numbers, declared arities, vectorized handlers).  This
    shim keeps the legacy surface byte-compatible: ``add`` forwards into
    ``table.register`` with lowest-free-slot allocation, which reproduces
    the old registration-order opcodes, and ``entries``/``by_name``/
    ``opcode``/``entry_for_opcode`` read straight through — the compiler
    and ``REXAVM._service_io`` never notice the swap.  New code should use
    ``vm.fios.table.register(...)`` (or ``REXAVM.svc_add``) directly.
    """

    def __init__(self):
        from repro.exec.syscalls import SyscallTable

        self.table = SyscallTable()

    @property
    def entries(self):
        return self.table.entries

    @property
    def by_name(self):
        return self.table.by_name

    def add(self, name: str, fn: Callable, args: int = 0, ret: int = 0) -> int:
        """fiosAdd (paper Def. 2). Returns the assigned opcode.

        Deprecated: registrations land in the numbered syscall table.
        """
        import warnings

        warnings.warn(
            "FiosRegistry.add is deprecated; register numbered syscalls via "
            "repro.exec.syscalls.SyscallTable (vm.fios.table.register)",
            DeprecationWarning,
            stacklevel=3,
        )
        return self.table.register(name, fn, args=args, ret=ret)

    def opcode(self, name: str) -> Optional[int]:
        return self.table.opcode(name)

    def entry_for_opcode(self, opcode: int):
        return self.table.entry_for_opcode(opcode)


@dataclass
class DiosEntry:
    name: str
    offset: int         # offset of the data (header cell is at offset-1)
    cells: int


class DiosRegistry:
    """Maps named host arrays into ``mem`` at MEM_BASE+offset.

    Layout per entry: [len, data...]; the VM name resolves to the address of
    data[0] so that array header conventions match frame-embedded arrays.
    """

    def __init__(self, mem_size: int):
        self.mem_size = mem_size
        self.free = 0
        self.entries: dict[str, DiosEntry] = {}

    def add(self, name: str, cells: int) -> DiosEntry:
        """diosAdd (paper Def. 2). Reserves [header + cells] in mem."""
        if name in self.entries:
            return self.entries[name]
        need = cells + 1
        if self.free + need > self.mem_size:
            raise MemoryError("DIOS mem exhausted")
        e = DiosEntry(name, self.free + 1, cells)
        self.free += need
        self.entries[name] = e
        return e

    def address(self, name: str) -> Optional[int]:
        e = self.entries.get(name)
        return None if e is None else MEM_BASE + e.offset

    def init_mem(self, mem: np.ndarray) -> None:
        """Write headers for all registered arrays into a mem buffer."""
        for e in self.entries.values():
            mem[e.offset - 1] = e.cells


class FleetIOService:
    """Gather/scatter host-IO service over the fleet's node axis.

    PR 1's ``FleetVM`` serviced host IO (FIOS calls, ``out``/``in``) by
    pulling the *entire* stacked ``VMState`` to the host and pushing all of
    it back — O(N · state) bytes per suspension even when one node of a
    thousand was waiting.  This service moves only the suspended slices:

      1. ``take_nodes(S, idx)`` gathers the suspended rows on device (a
         cross-shard gather when the node axis is mesh-sharded) and
         ``device_get`` pulls just those rows;
      2. each suspended node's host frontend gets its fresh slice and runs
         ``REXAVM._service_io(route_net=False)`` exactly as before (FIOS
         callbacks may mutate ``mem`` via ``dios_write`` — the slice is the
         node's canonical state for the duration);
      3. ``put_nodes(S, idx, slices)`` scatters the serviced rows back.

    ``d2h_bytes``/``h2d_bytes`` count the rows actually moved, so the
    partial-IO win over a full sync is measurable (bench_vm's fleet case).
    """

    def __init__(self, nodes: "list[REXAVM]"):
        self.nodes = list(nodes)
        self.services = 0            # service invocations
        self.nodes_serviced = 0      # node-slices moved (both directions)
        self.d2h_bytes = 0
        self.h2d_bytes = 0
        self.tracer = None           # the fleet's repro.obs.RoundTracer

    def service(self, S, node_idx) -> tuple[object, bool]:
        """Service host-IO suspensions of ``node_idx`` against device state
        ``S`` (a stacked fleet ``VMState``).  Returns ``(S', progress)``.
        Spanned as ``fleet.io_service`` with the suspended nodes' count
        when the fleet has attached its tracer."""
        if self.tracer is None:
            return self._service(S, node_idx)
        with self.tracer.span("io_service", nodes=len(node_idx)):
            return self._service(S, node_idx)

    def _service(self, S, node_idx) -> tuple[object, bool]:
        import jax

        from repro.core.vm import vmstate as vms
        from repro.core.vm.vmstate import VMState

        node_idx = [int(i) for i in node_idx]
        if not node_idx:
            return S, False
        sub = vms.take_nodes(S, np.asarray(node_idx, np.int32))
        host = jax.device_get(sub)
        moved = vms.state_nbytes(host)
        self.d2h_bytes += moved
        progress = False
        for j, i in enumerate(node_idx):
            vm = self.nodes[i]
            # np.array keeps 0-d fields as mutable 0-d arrays, not scalars.
            vm.state = VMState(*[np.array(f[j]) for f in host])
            progress |= vm._service_io(route_net=False)
        back = vms.stack_states([self.nodes[i].state for i in node_idx])
        self.h2d_bytes += vms.state_nbytes(back)
        S = vms.put_nodes(S, np.asarray(node_idx, np.int32), back)
        self.services += 1
        self.nodes_serviced += len(node_idx)
        return S, progress


class HostLink:
    """Host-routed inter-node message bus (the pre-fleet transport).

    Wires every node's ``on_send`` callback so that ``v dst send`` lands in
    node ``dst``'s ``recv_queue`` tagged with the sender's index;
    out-of-range destinations are dropped and recorded.  Unlike the fleet's
    on-device mailbox rings, ``recv_queue`` is unbounded — there is no
    backpressure, so a flooding sender is never throttled.  Each message
    costs a host round trip per node slice; use
    :class:`repro.core.vm.fleet.FleetVM` to keep whole message rounds on
    device.
    """

    def __init__(self, nodes: "list[REXAVM]"):
        self.nodes = list(nodes)
        self.dropped: list[tuple[int, int, int]] = []   # (src, dst, value)
        for src, vm in enumerate(self.nodes):
            vm.on_send = self._make_on_send(src)

    def _make_on_send(self, src: int) -> Callable[[int, int], None]:
        def on_send(dst: int, value: int) -> None:
            if 0 <= dst < len(self.nodes):
                self.nodes[dst].recv_queue.append((src, value))
            else:
                self.dropped.append((src, dst, value))
        return on_send
