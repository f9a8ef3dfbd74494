"""The Auditor's verdict memo: each distinct program is verified once.

``FleetVM`` audits every node at every ``start()``, and most nodes of a
fleet run one of a few firmwares from the same task entries.  Their code
segments still differ, because the frame's variables and arrays (a
node's samples, its LCG state) live in the code segment too.  The
verifier and the branch-set walk read the code only through
``cfg.decode``, which reads
``cs[pc]``, ``cs[pc + 1]`` for an operand, and ``len(cs)``.  A node's
analysis is therefore a function of

* the key: its live task entries, its syscall arities, its dictionary
  symbols, and the length and type of its code segment;
* the cells the analysis decoded (its footprint);
* the ISA and the ``VMConfig``, which one fleet's nodes share.

A verdict records the footprint's addresses and cells beside the report
and the branch sets.  A node with the same key whose code holds the same
cells at those addresses gets the same verdict.  That is exact by
construction: analysing its code would decode the same addresses, see the
same values and reach the same result.  A change to any decoded cell
misses; a change to a cell the analysis never decoded, such as a
variable's, hits.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.analysis.feasibility import predict_branch_sets
from repro.analysis.verifier import (
    ProgramReport,
    analyze_vm,
    live_entries,
    node_tables,
)


@dataclass(frozen=True)
class Verdict:
    """One verified program: its footprint and what the analysis gave."""

    addrs: np.ndarray          # sorted code addresses decoded (intp)
    cells: bytes               # the code's bytes at ``addrs``
    report: ProgramReport      # shared by every node that reuses it: read-only
    sets: tuple                # predict_branch_sets from the first entry


def node_key(vm) -> tuple:
    """What a node's analysis takes besides its code cells.  The symbols
    keep the dictionary's order: where two names share an address, the
    diagnostics name the function by the one that comes last."""
    effects, symbols = node_tables(vm)
    cs = vm.state.cs
    return (
        tuple(live_entries(vm.state)),
        tuple(effects.items()),
        tuple(symbols.items()),
        len(cs),
        np.asarray(cs).dtype.str,
    )


def verify(vm) -> Verdict:
    """Run the verifier and the branch-set walk on ``vm``'s code from its
    live entries, recording the cells they decode."""
    cs = np.asarray(vm.state.cs)
    reads: set[int] = set()
    report = analyze_vm(vm, reads=reads)
    sets = (
        predict_branch_sets(cs, report.entries[0].pc, vm.isa, reads=reads)
        if report.entries else ()
    )
    addrs = np.array(sorted(reads), np.intp)
    return Verdict(addrs, _cells(cs, addrs), report, sets)


def _cells(cs, addrs) -> bytes:
    return cs[addrs].tobytes()


def _find(footprints, cs) -> Verdict | None:
    """The verdict under one key whose cells ``cs`` holds: one slice and
    one dict probe per distinct footprint.  At most one can match, since
    analysing ``cs`` would decode exactly the matching footprint."""
    for addrs, by_cells in (footprints or {}).values():
        v = by_cells.get(_cells(cs, addrs))
        if v is not None:
            return v
    return None


class VerdictMemo:
    """Verdicts of one fleet's nodes, keyed by :func:`node_key`, then by
    footprint, then by the cells there.  Nodes of one structure that
    differ in a decoded cell (a per-node literal) share one footprint.

    Each :meth:`audit` keeps only the verdicts it reused or created, so
    the memo holds the fleet's current program mix, not its history.
    """

    def __init__(self):
        # key -> {addrs bytes: (addrs, {cells: Verdict})}
        self._memo: dict[tuple, dict[bytes, tuple]] = {}

    def __len__(self) -> int:
        return sum(len(by_cells) for fps in self._memo.values()
                   for _, by_cells in fps.values())

    def audit(self, nodes) -> tuple[list, list, int]:
        """``(reports, sets, verified)``: per node its ``ProgramReport``
        and its predicted branch sets (``()`` where none), and how many
        nodes were verified afresh; the others reused a verdict."""
        old, self._memo = self._memo, {}
        reports, sets = [], []
        verified = 0
        for vm in nodes:
            key = node_key(vm)
            cs = np.asarray(vm.state.cs)
            fps = self._memo.setdefault(key, {})
            v = _find(fps, cs)
            if v is None:
                v = _find(old.get(key), cs)
                if v is None:
                    v = verify(vm)
                    verified += 1
                fp = fps.setdefault(v.addrs.tobytes(), (v.addrs, {}))
                fp[1][v.cells] = v
            reports.append(v.report)
            sets.append(v.sets)
        return reports, sets, verified
