"""Step semantics of the vmloop Pallas kernel.

The kernel's contract is *byte-exactness* with the lax interpreter
(``repro.core.vm.interp``) and the Python ``Oracle`` over the opcode subset
it claims — the paper's software/hardware operational-equivalence claim
restated for a TPU kernel backend.  The kernel-side fetch/decode/execute
step is written once here, over one node's machine state held in a flat
int32 *row* (:class:`Layout`) that it reads and writes through a ref, and
run by ``vmloop.vmloop_call``, the ``pl.pallas_call`` kernel, with the
node's row DMA'd into scalar memory (SMEM).

Every state access is a scalar load or store at a computed offset of the
row, so the step lowers on a TPU: Mosaic has no value-level
``dynamic_slice``/gather/scatter, but scalar memory takes dynamic
addresses.  Reads clamp each index into its field (jnp's gather rule);
writes outside a field (the ``mode="drop"`` sites of ``interp.py``) land
on a trash cell past the state.  Vector words loop over their window one
element at a time, computing into a scratch window before any write, so
overlapping source and destination windows behave as the lax
interpreter's whole-vector reads do.

Relative to ``interp.py`` this is a deliberate *independent transliteration*
of the step semantics, exactly as ``oracle.py`` is for plain Python: the
equivalence suite only proves something because the engines do not share
one step definition.  The price is hand-synchronization — any semantic
change to ``interp.Interpreter._build`` (op bodies, stack pre-check,
exception dispatch) MUST be mirrored in :func:`make_core_step`, and
tests/test_vm_pallas.py (per-opcode sweep + randomized fleet programs) is
the tripwire that catches a missed mirror.

Opcode classification
---------------------
``SUPPORTED_WORDS`` is the claimed set — now nearly the whole ISA: stack,
arithmetic (incl. the 64-bit-exact ``*/``), comparison, bitwise, scalar and
vector memory, control flow, ``dlit``, the non-spawning task words, the
exception machinery, printing into the out ring, the IO-suspending words
(``out``/``in``/``send``/``receive`` execute their *suspension* in-kernel:
pc rewind + ``io_op`` + ST_IOWAIT, then the loop exits on the status change
with ``bailed`` false — delivery stays with the host service loop and the
collective router between kernel invocations), the LUT fixed-point DSP
scalars (``sin``/``log``/``sigmoid``/``relu``/``sqrt`` as table loads; the
tables ride as a kernel operand), and the vector/ANN ops
(``vecfold``/``dotprod`` accumulate int32 products element by element —
byte-exact with ``interp``'s sum of products because int32 wraparound
addition is order-independent; ``lowp``/``highp``/``hull`` are a short
IIR scan).

``BAILOUT_WORDS`` is down to ``task`` (spawning writes prio/deadline and
arbitrary task slots outside CoreState) and ``rnd`` (the LCG state is
uint32 while the row is int32).  On the first declined (or unknown/FIOS)
opcode the loop *bails out before executing it*, reporting how many
instructions it did run plus *which opcode* bailed (``bail_op``, feeding
the per-opcode bail histogram in ``pallas_stats()``), so the host-side lax
path can finish the slice from a byte-identical intermediate state.  Every
ISA word MUST appear in exactly one of the two sets — ``supported_mask``
raises otherwise, and the ISA coverage test sweeps the claim.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import VMConfig
from repro.core.fixedpoint import fpsqrt_jnp
from repro.core.fixedpoint.luts import (
    LOG10_LUT,
    SGLUT13,
    SGLUT310,
    _SIN_QUARTER,
    _TWO_PI_MR,
)
from repro.core.vm.interp import STACK_NEEDS, _muldiv, _truncdiv, _truncmod
from repro.core.vm.spec import (
    EXC_BOUNDS,
    EXC_DIVBYZERO,
    EXC_STACK,
    EXC_TRAP,
    ISA,
    MEM_BASE,
    NUM_EXC,
    ST_DONE,
    ST_ERR,
    ST_EVENT,
    ST_FREE,
    ST_HALT,
    ST_IOWAIT,
    ST_RUN,
    ST_SLEEP,
    ST_YIELD,
    get_isa,
)
from repro.core.vm.vmstate import OUT_CHR, OUT_NUM, VMState

I32 = jnp.int32

# VMState fields the supported opcode set can read or write, in VMState
# order.  Everything else (mailboxes, rng, prio/deadline) belongs to the
# declined opcodes and the between-rounds router and never enters the
# kernel.
CORE_FIELDS = (
    "cs", "mem", "ds", "rs", "fs",
    "dsp", "rsp", "fsp", "pc", "tstatus",
    "timeout", "ev_addr", "ev_val",
    "catch_pc", "catch_rsp", "pending_exc", "last_exc",
    "io_op", "handlers", "cur", "now", "steps",
    "out", "outp",
)
SCALAR_FIELDS = ("cur", "now", "steps", "outp")
READONLY_FIELDS = ("cur", "now")      # never written by a supported opcode
MUTATED_FIELDS = tuple(f for f in CORE_FIELDS if f not in READONLY_FIELDS)


class Tables(NamedTuple):
    """Constant dispatch + LUT tables, passed as one flat kernel operand (a
    Pallas kernel cannot close over array constants).  All int32.  The five
    ``(num_ops + 1,)`` dispatch tables: ``sup`` is the opcode claim mask
    (0/1), the rest are the stack-effect pre-check of ``interp.exec_op``.
    The four fixed-point LUTs back the DSP scalar words: ``log10`` (90,),
    ``sg13`` (24,), ``sg310`` (6,), ``sinq`` (256,)."""

    sup: jnp.ndarray
    din: jnp.ndarray
    dout: jnp.ndarray
    fin: jnp.ndarray
    fout: jnp.ndarray
    log10: jnp.ndarray
    sg13: jnp.ndarray
    sg310: jnp.ndarray
    sinq: jnp.ndarray


class CoreState(NamedTuple):
    """One node's kernel-visible machine state (see CORE_FIELDS)."""

    cs: jnp.ndarray          # (CS,)
    mem: jnp.ndarray         # (MEM,)
    ds: jnp.ndarray          # (T, DS)
    rs: jnp.ndarray          # (T, RS)
    fs: jnp.ndarray          # (T, FS)
    dsp: jnp.ndarray         # (T,)
    rsp: jnp.ndarray         # (T,)
    fsp: jnp.ndarray         # (T,)
    pc: jnp.ndarray          # (T,)
    tstatus: jnp.ndarray     # (T,)
    timeout: jnp.ndarray     # (T,)
    ev_addr: jnp.ndarray     # (T,)
    ev_val: jnp.ndarray      # (T,)
    catch_pc: jnp.ndarray    # (T,)
    catch_rsp: jnp.ndarray   # (T,)
    pending_exc: jnp.ndarray # (T,)
    last_exc: jnp.ndarray    # (T,)
    io_op: jnp.ndarray       # (T,)
    handlers: jnp.ndarray    # (NUM_EXC,)
    cur: jnp.ndarray         # ()
    now: jnp.ndarray         # ()  read-only
    steps: jnp.ndarray       # ()
    out: jnp.ndarray         # (2 * OUTN,)
    outp: jnp.ndarray        # ()


# --- opcode classification (must partition the whole word list) -------------

SUPPORTED_WORDS = (
    # stack
    "nop", "dup", "drop", "swap", "over", "rot", "nip", "tuck", "pick",
    "2dup", "2drop", "depth",
    # arithmetic
    "+", "-", "*", "/", "mod", "*/", "negate", "abs", "min", "max",
    "1+", "1-", "2*", "2/",
    # comparison
    "=", "<>", "<", ">", "<=", ">=", "0=", "0<", "0>",
    # bitwise
    "and", "or", "xor", "invert", "lshift", "rshift",
    # scalar memory (unified CS/DIOS address space) + wide fill
    "@", "!", "+!", "get", "put", "push", "pop", "len", "fill",
    # control flow
    "branch", "0branch", "ret", "exit", "exec",
    "doinit", "doloop", "i", "j", "unloop", "halt", "end",
    # literals
    "dlit",
    # printing into the out ring
    ".", "emit", "cr", "prstr", "vecprint",
    # IO suspension (pc rewind + io_op + ST_IOWAIT, executed in-kernel;
    # delivery stays with the host service / collective router)
    "out", "in", "send", "receive",
    # tasks (non-spawning)
    "yield", "sleep", "await", "taskid", "ms", "steps",
    # exceptions
    "exception", "catch", "throw",
    # LUT fixed-point DSP scalars
    "sin", "log", "sigmoid", "relu", "sqrt",
    # vector / ANN ops
    "vecload", "vecscale", "vecadd", "vecmul", "vecfold", "vecmap",
    "dotprod", "vecmax", "hull", "lowp", "highp",
)

BAILOUT_WORDS = (
    # task spawn writes prio/deadline + arbitrary task slots (outside
    # CoreState); rnd advances the uint32 LCG (the row is int32).
    "task", "rnd",
)


def supported_mask(isa: ISA | None = None) -> np.ndarray:
    """(num_ops + 1,) bool: kernel-claimed opcodes.  Index ``num_ops`` (the
    clip target for out-of-table opcodes, i.e. FIOS calls and traps) is
    always False.  Raises if any ISA word is unclassified or double-listed —
    adding a word to the ISA forces an explicit claim/decline here."""
    isa = isa or get_isa()
    sup, bail = set(SUPPORTED_WORDS), set(BAILOUT_WORDS)
    both = sup & bail
    if both:
        raise RuntimeError(f"words claimed and declined: {sorted(both)}")
    mask = np.zeros(isa.num_ops + 1, bool)
    for code in range(isa.num_ops):
        nm = isa.name[code]
        if nm in sup:
            mask[code] = True
        elif nm not in bail:
            raise RuntimeError(
                f"ISA word {nm!r} is neither in SUPPORTED_WORDS nor "
                f"BAILOUT_WORDS — classify it for the vmloop kernel"
            )
    return mask


def make_tables(isa: ISA | None = None) -> Tables:
    """Numpy dispatch + LUT tables for one ISA (see :class:`Tables`)."""
    isa = isa or get_isa()
    num_ops = isa.num_ops
    sup = supported_mask(isa)
    din = np.zeros(num_ops + 1, np.int32)
    dout = np.zeros(num_ops + 1, np.int32)
    fin = np.zeros(num_ops + 1, np.int32)
    fout = np.zeros(num_ops + 1, np.int32)
    for code in range(num_ops):
        d_in, d_out, f_in, f_out = STACK_NEEDS.get(isa.name[code], (0, 0, 0, 0))
        din[code], dout[code] = d_in, d_out
        fin[code], fout[code] = f_in, f_out
    return Tables(
        sup=sup.astype(np.int32), din=din, dout=dout, fin=fin, fout=fout,
        log10=np.asarray(LOG10_LUT, np.int32),
        sg13=np.asarray(SGLUT13, np.int32),
        sg310=np.asarray(SGLUT310, np.int32),
        sinq=np.asarray(_SIN_QUARTER, np.int32),
    )


def flat_tables(isa: ISA | None = None) -> tuple[np.ndarray, dict]:
    """All of :func:`make_tables` concatenated into one int32 vector, plus
    each table's ``(offset, length)`` in it."""
    tables = make_tables(isa)
    spans, pos = {}, 0
    for name, t in zip(Tables._fields, tables):
        spans[name] = (pos, len(t))
        pos += len(t)
    return np.concatenate(list(tables)).astype(np.int32), spans


# --- VMState <-> CoreState ---------------------------------------------------

def core_of(S: VMState) -> CoreState:
    """Extract the kernel-visible fields (works stacked or single-node)."""
    return CoreState(*[getattr(S, f) for f in CORE_FIELDS])


def merge_core(S: VMState, core: CoreState) -> VMState:
    """Write the kernel's mutated fields back into the full state."""
    return S._replace(**{f: getattr(core, f) for f in MUTATED_FIELDS})


# --- one node's flat row -----------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class Layout:
    """Where each :class:`CoreState` field lives in a node's flat int32 row.

    The CoreState fields come first, in ``CORE_FIELDS`` order and row-major
    each.  After them: the loop's results (``n_exec``, ``bailed``,
    ``bail_op``), the obs retirement histogram (``num_ops + 4`` bins), a
    ``max_vec`` scratch window for the vector words, and the trash cell
    that dropped writes land on.  The width is padded to whole 128-lane
    rows."""

    def __init__(self, cfg: VMConfig, isa: ISA | None = None):
        isa = isa or get_isa()
        T = cfg.max_tasks
        per_task = (T,)
        self.shape: dict[str, tuple[int, ...]] = {
            "cs": (cfg.cs_size,), "mem": (cfg.mem_size,),
            "ds": (T, cfg.ds_size), "rs": (T, cfg.rs_size),
            "fs": (T, cfg.fs_size),
            "handlers": (NUM_EXC,), "out": (2 * cfg.out_ring_size,),
            **{f: () for f in SCALAR_FIELDS},
        }
        self.off: dict[str, int] = {}
        pos = 0
        for f in CORE_FIELDS:
            shp = self.shape.setdefault(f, per_task)
            self.off[f] = pos
            pos += int(np.prod(shp, dtype=np.int64))
        self.core_words = pos
        self.n_exec, self.bailed, self.bail_op = pos, pos + 1, pos + 2
        self.hist = pos + 3
        self.nbins = isa.num_ops + 4
        self.tmp = self.hist + self.nbins
        self.trash = self.tmp + cfg.max_vec
        self.width = _round_up(self.trash + 1, 128)

    def pack(self, core: CoreState) -> jnp.ndarray:
        """Stacked CoreState (node-leading) -> ``(N, 1, width)`` rows."""
        N = core.pc.shape[0]
        cols = [getattr(core, f).reshape(N, -1) for f in CORE_FIELDS]
        cols.append(jnp.zeros((N, self.width - self.core_words), I32))
        return jnp.concatenate(cols, axis=1).reshape(N, 1, self.width)

    def unpack(self, rows: jnp.ndarray):
        """``(N, 1, width)`` rows -> ``(core, n_exec, bailed, bail_op,
        op_hist)``, each node-leading."""
        N = rows.shape[0]
        flat = rows.reshape(N, self.width)

        def cut(off, shp):
            size = int(np.prod(shp, dtype=np.int64))
            return flat[:, off:off + size].reshape((N,) + shp)

        core = CoreState(
            **{f: cut(self.off[f], self.shape[f]) for f in CORE_FIELDS}
        )
        return (
            core,
            flat[:, self.n_exec],
            flat[:, self.bailed] != 0,
            flat[:, self.bail_op],
            cut(self.hist, (self.nbins,)),
        )


class _Node:
    """One node's CoreState seen through its row ref ``(1, width)`` and the
    flat table ref.  ``get`` clamps each index into its field; ``set``
    drops writes outside the field (or with ``when`` false) onto the trash
    cell.  ``t`` is the current task (``cur`` is read-only in the loop)."""

    def __init__(self, lay: Layout, row, tab, spans: dict):
        self.lay, self.row, self.tab_ref, self.spans = lay, row, tab, spans
        self.t = self.get("cur")

    def _lin(self, f, idx, clamp):
        lin, ok = I32(0), jnp.bool_(True)
        for i, n in zip(idx, self.lay.shape[f]):
            i = jnp.asarray(i, I32)
            if clamp:
                i = jnp.clip(i, 0, n - 1)
            else:
                ok = ok & (i >= 0) & (i < n)
            lin = lin * n + i
        return self.lay.off[f] + lin, ok

    def get(self, f, *idx):
        flat, _ = self._lin(f, idx, clamp=True)
        return self.row[0, flat]

    def set(self, f, v, *idx, when=None):
        flat, ok = self._lin(f, idx, clamp=False)
        if when is not None:
            ok = ok & when
        self.row[0, jnp.where(ok, flat, self.lay.trash)] = jnp.asarray(v, I32)

    def cell(self, k):
        return self.row[0, k]

    def set_cell(self, k, v):
        self.row[0, k] = jnp.asarray(v, I32)

    def tab(self, name, i):
        off, n = self.spans[name]
        return self.tab_ref[off + jnp.clip(i, 0, n - 1)]


def _loop(n, body):
    """``body(k)`` for k in [0, n), n traced or static."""
    def step(k, carry):
        body(k)
        return carry
    lax.fori_loop(0, n, step, I32(0))


def _tree_switch(index, branches):
    """``lax.switch`` over zero-operand branches as a balanced tree of
    two-way conds: Mosaic lowers an n-way switch to an n-deep if/else
    cascade, which its canonicalizer cannot take at the ISA's width."""
    if len(branches) == 1:
        return branches[0]()
    mid = len(branches) // 2
    return lax.cond(
        index < mid,
        lambda: _tree_switch(index, branches[:mid]),
        lambda: _tree_switch(index - mid, branches[mid:]),
    )


# --- LUT fixed-point scalars (mirror fixedpoint.luts *_jnp, but read the
# --- tables from the kernel operand instead of module-level constants) -------

def _fplog10_t(x, m: _Node):
    x = jnp.maximum(x.astype(I32), 10)
    shift = jnp.zeros_like(x)
    for _ in range(3):
        big = x >= 100
        shift = shift + big.astype(I32)
        x = jnp.where(big, x // 10, x)
    return shift * 100 + m.tab("log10", x - 10)


def _fpsigmoid_t(x, m: _Node):
    x = x.astype(I32)
    mirror = x < 0
    ax = jnp.abs(x)
    y1 = 500 + (ax * 231) // 1000
    i13 = jnp.clip(_fplog10_t(ax // 5, m) // 2 - 65, 0, 23)
    y2 = m.tab("sg13", i13) + 731
    i310 = jnp.clip(_fplog10_t(ax // 10, m) // 10 - 14, 0, 5)
    y3 = m.tab("sg310", i310) + 952
    y = jnp.where(ax <= 1000, y1, jnp.where(ax < 3000, y2, y3))
    y = jnp.where(ax >= 10000, 1000, y)
    return jnp.where(mirror, 1000 - y, y)


def _fpsin_t(x, m: _Node):
    x = jnp.mod(x.astype(I32), _TWO_PI_MR)
    x = jnp.where(x < 0, x + _TWO_PI_MR, x)
    t = x * 1024 // _TWO_PI_MR
    quad = t // 256
    idx = t % 256
    up = m.tab("sinq", idx)
    down = m.tab("sinq", 255 - idx)
    mag = jnp.where((quad % 2) == 0, up, down)
    return jnp.where(quad >= 2, -mag, mag)


# --- the step function (mirrors interp.step_instr over one node's row) -------

class CoreStep(NamedTuple):
    """The step functions over a :class:`_Node` (see make_core_step)."""

    step: Callable        # m -> None: execute one instruction in place
    supported: Callable   # m -> bool: the next instruction may run here
    retire_bin: Callable  # m -> int32: its obs histogram bin
    fetch: Callable       # m -> (pc, pc_ok, tag, payload)


def make_core_step(
    cfg: VMConfig, isa: ISA | None = None, elide_checks: bool = False
) -> CoreStep:
    """Returns the :class:`CoreStep` over a :class:`_Node`.

    ``step_instr(m)`` executes one instruction in place; it is a
    transliteration of :meth:`repro.core.vm.interp.Interpreter._build`'s
    step for the supported subset — same helpers, same clip patterns, same
    exception dispatch — so a supported instruction produces bit-identical
    state on either engine.  ``instr_supported(m)`` is the bail predicate,
    evaluated on the *fetched* instruction before any state is touched.

    ``elide_checks=True`` drops the table-driven stack pre-check and the
    TAG_LIT push-overflow check at build time (the flag is static, so the
    check computation vanishes from the kernel, not just its outcome) —
    only sound for programs the static verifier proved EXC_STACK-free,
    mirroring ``interp.Interpreter(elide_checks=True)``.
    """
    isa = isa or get_isa()
    CS, MEM = cfg.cs_size, cfg.mem_size
    DS, RS, FS = cfg.ds_size, cfg.rs_size, cfg.fs_size
    MV = cfg.max_vec
    OUTN = cfg.out_ring_size

    # -- low-level helpers (identical to interp._build) ----------------------

    def dpeek(m, k=1):
        return m.get("ds", m.t, jnp.maximum(m.get("dsp", m.t) - k, 0))

    def dpop1(m):
        return dpopn(m, 1)[0]

    def dpopn(m, n):
        dsp = m.get("dsp", m.t)
        vals = tuple(
            m.get("ds", m.t, jnp.maximum(dsp - n + k, 0)) for k in range(n)
        )
        m.set("dsp", dsp - n, m.t)
        return vals

    def dpush(m, v):
        dsp = m.get("dsp", m.t)
        m.set("ds", v, m.t, jnp.clip(dsp, 0, DS - 1))
        m.set("dsp", dsp + 1, m.t)

    def fpush(m, v):
        fsp = m.get("fsp", m.t)
        m.set("fs", v, m.t, jnp.clip(fsp, 0, FS - 1))
        m.set("fsp", fsp + 1, m.t)

    def set_pc(m, pc, when=None):
        m.set("pc", pc, m.t, when=when)

    def cur_pc(m):
        return m.get("pc", m.t)

    def raise_exc(m, code, when=None):
        p = m.get("pending_exc", m.t)
        m.set("pending_exc", jnp.where(p == 0, code, p), m.t, when=when)

    def set_status(m, s, when=None):
        m.set("tstatus", s, m.t, when=when)

    def addr_valid(addr):
        in_cs = (addr >= 0) & (addr < CS)
        in_mem = (addr >= MEM_BASE) & (addr < MEM_BASE + MEM)
        return in_cs | in_mem

    def mread(m, addr):
        cs_v = m.get("cs", jnp.clip(addr, 0, CS - 1))
        mem_v = m.get("mem", jnp.clip(addr - MEM_BASE, 0, MEM - 1))
        return jnp.where(addr >= MEM_BASE, mem_v, cs_v)

    def mwrite(m, addr, v, when=True):
        in_mem = addr >= MEM_BASE
        m.set("cs", v, jnp.clip(addr, 0, CS - 1), when=~in_mem & when)
        m.set("mem", v, jnp.clip(addr - MEM_BASE, 0, MEM - 1), when=in_mem & when)

    # A vector operand at ``addr`` lives wholly in CS or wholly in DIOS,
    # chosen by its base address; element k reads/writes addr + k clipped
    # into that memory (interp's vread/vwrite, one lane at a time).

    def velem(m, addr, k):
        idx = addr + k
        cs_v = m.get("cs", jnp.clip(idx, 0, CS - 1))
        mem_v = m.get("mem", jnp.clip(idx - MEM_BASE, 0, MEM - 1))
        return jnp.where(addr >= MEM_BASE, mem_v, cs_v)

    def vset(m, addr, k, v):
        idx = addr + k
        in_mem = addr >= MEM_BASE
        m.set("cs", v, jnp.clip(idx, 0, CS - 1), when=~in_mem)
        m.set("mem", v, jnp.clip(idx - MEM_BASE, 0, MEM - 1), when=in_mem)

    def vlen(m, addr):
        """A vector's length from its header cell, clipped to the window."""
        return jnp.clip(mread(m, addr - 1), 0, MV)

    def vcompute(m, dst, ln, elem):
        """dst[k] = elem(k) for k < ln: every element is computed into the
        scratch window before any is written, as interp reads whole
        vectors before its scatter."""
        _loop(ln, lambda k: m.set_cell(m.lay.tmp + k, elem(k)))
        _loop(ln, lambda k: vset(m, dst, k, m.cell(m.lay.tmp + k)))

    def out_write(m, kind, val):
        p = m.get("outp")
        ok = p < OUTN
        m.set("out", kind, 2 * p, when=ok)
        m.set("out", val, 2 * p + 1, when=ok)
        m.set("outp", jnp.where(ok, p + 1, p))

    # scale-vector application (paper Tab. 5 semantics) -----------------------

    def vscale(vals, svals, s_on):
        expanded = vals * jnp.where(svals > 0, svals, 1)
        divisor = jnp.where(svals < 0, -svals, 1)
        reduced = jnp.sign(vals) * (jnp.abs(vals) // divisor)
        scaled = jnp.where(svals > 0, expanded, jnp.where(svals < 0, reduced, vals))
        return jnp.where(s_on, scaled, vals)

    def scaled_elem(m, saddr, val_of):
        """k -> val_of(k) scaled by the optional scale vector at saddr."""
        s_on = saddr != 0
        sbase = jnp.where(s_on, saddr, I32(1))
        return lambda k: vscale(val_of(k), velem(m, sbase, k), s_on)

    # -- opcode implementations (each mutates the node in place) -------------

    def bin_op(f):
        def op(m):
            a, b = dpopn(m, 2)
            dpush(m, f(a, b))
        return op

    def un_op(f):
        def op(m):
            dpush(m, f(dpop1(m)))
        return op

    def un_op_t(f):
        def op(m):
            dpush(m, f(dpop1(m), m))
        return op

    def cmp_op(f):
        return bin_op(lambda a, b: jnp.where(f(a, b), I32(-1), I32(0)))

    B: dict[str, Callable] = {}

    B["nop"] = lambda m: None
    B["dup"] = lambda m: dpush(m, dpeek(m))
    B["drop"] = dpop1

    def op_swap(m):
        a, b = dpopn(m, 2)
        dpush(m, b)
        dpush(m, a)
    B["swap"] = op_swap

    B["over"] = lambda m: dpush(m, dpeek(m, 2))

    def op_rot(m):
        a, b, c = dpopn(m, 3)
        for v in (b, c, a):
            dpush(m, v)
    B["rot"] = op_rot

    def op_nip(m):
        _, b = dpopn(m, 2)
        dpush(m, b)
    B["nip"] = op_nip

    def op_tuck(m):
        a, b = dpopn(m, 2)
        for v in (b, a, b):
            dpush(m, v)
    B["tuck"] = op_tuck

    def op_pick(m):
        n = dpop1(m)
        dsp = m.get("dsp", m.t)
        idx = jnp.clip(dsp - 1 - n, 0, DS - 1)
        bad = (n < 0) | (n >= dsp)
        dpush(m, m.get("ds", m.t, idx))
        raise_exc(m, EXC_STACK, when=bad)
    B["pick"] = op_pick

    def op_2dup(m):
        a, b = dpeek(m, 2), dpeek(m, 1)
        dpush(m, a)
        dpush(m, b)
    B["2dup"] = op_2dup

    B["2drop"] = lambda m: dpopn(m, 2)
    B["depth"] = lambda m: dpush(m, m.get("dsp", m.t))

    B["+"] = bin_op(lambda a, b: a + b)
    B["-"] = bin_op(lambda a, b: a - b)
    B["*"] = bin_op(lambda a, b: a * b)

    def div_like(nargs, f):
        def op(m):
            args = dpopn(m, nargs)
            dpush(m, f(*args))
            raise_exc(m, EXC_DIVBYZERO, when=args[-1] == 0)
        return op

    B["/"] = div_like(2, _truncdiv)
    B["mod"] = div_like(2, _truncmod)
    B["*/"] = div_like(3, _muldiv)

    B["negate"] = un_op(lambda v: -v)
    B["abs"] = un_op(jnp.abs)
    B["min"] = bin_op(jnp.minimum)
    B["max"] = bin_op(jnp.maximum)
    B["1+"] = un_op(lambda v: v + 1)
    B["1-"] = un_op(lambda v: v - 1)
    B["2*"] = un_op(lambda v: v * 2)
    B["2/"] = un_op(lambda v: v >> 1)

    B["="] = cmp_op(lambda a, b: a == b)
    B["<>"] = cmp_op(lambda a, b: a != b)
    B["<"] = cmp_op(lambda a, b: a < b)
    B[">"] = cmp_op(lambda a, b: a > b)
    B["<="] = cmp_op(lambda a, b: a <= b)
    B[">="] = cmp_op(lambda a, b: a >= b)
    B["0="] = un_op(lambda v: jnp.where(v == 0, I32(-1), I32(0)))
    B["0<"] = un_op(lambda v: jnp.where(v < 0, I32(-1), I32(0)))
    B["0>"] = un_op(lambda v: jnp.where(v > 0, I32(-1), I32(0)))

    B["and"] = bin_op(jnp.bitwise_and)
    B["or"] = bin_op(jnp.bitwise_or)
    B["xor"] = bin_op(jnp.bitwise_xor)
    B["invert"] = un_op(jnp.bitwise_not)
    B["lshift"] = bin_op(lambda a, n: a << (n & 31))
    B["rshift"] = bin_op(lambda a, n: a >> (n & 31))

    def op_fetch(m):
        addr = dpop1(m)
        dpush(m, mread(m, addr))
        raise_exc(m, EXC_BOUNDS, when=~addr_valid(addr))
    B["@"] = op_fetch

    def op_store(m):
        v, addr = dpopn(m, 2)
        mwrite(m, addr, v)
        raise_exc(m, EXC_BOUNDS, when=~addr_valid(addr))
    B["!"] = op_store

    def op_addstore(m):
        v, addr = dpopn(m, 2)
        mwrite(m, addr, mread(m, addr) + v)
        raise_exc(m, EXC_BOUNDS, when=~addr_valid(addr))
    B["+!"] = op_addstore

    def op_get(m):
        n, arr = dpopn(m, 2)
        ln = mread(m, arr - 1)
        bad = (n < 0) | (n >= ln)
        dpush(m, mread(m, arr + jnp.clip(n, 0, jnp.maximum(ln - 1, 0))))
        raise_exc(m, EXC_BOUNDS, when=bad)
    B["get"] = op_get

    def op_put(m):
        v, n, arr = dpopn(m, 3)
        ln = mread(m, arr - 1)
        bad = (n < 0) | (n >= ln)
        mwrite(m, arr + n, v, when=~bad)
        raise_exc(m, EXC_BOUNDS, when=bad)
    B["put"] = op_put

    def op_push(m):
        v, arr = dpopn(m, 2)
        top = mread(m, arr)
        ln = mread(m, arr - 1)
        bad = top + 1 >= ln
        raise_exc(m, EXC_BOUNDS, when=bad)
        mwrite(m, arr + top + 1, v, when=~bad)
        mwrite(m, arr, top + 1, when=~bad)
    B["push"] = op_push

    def op_pop(m):
        arr = dpop1(m)
        top = mread(m, arr)
        bad = top <= 0
        v = mread(m, arr + jnp.maximum(top, 1))
        dpush(m, jnp.where(bad, 0, v))
        raise_exc(m, EXC_BOUNDS, when=bad)
        mwrite(m, arr, top - 1, when=~bad)
    B["pop"] = op_pop

    def op_fill(m):
        v, arr = dpopn(m, 2)
        _loop(vlen(m, arr), lambda k: vset(m, arr, k, v))
    B["fill"] = op_fill

    B["len"] = lambda m: dpush(m, mread(m, dpop1(m) - 1))

    # control ----------------------------------------------------------------

    B["branch"] = lambda m: set_pc(m, m.get("cs", jnp.clip(cur_pc(m), 0, CS - 1)))

    def op_0branch(m):
        f = dpop1(m)
        pc = cur_pc(m)
        tgt = m.get("cs", jnp.clip(pc, 0, CS - 1))
        set_pc(m, jnp.where(f == 0, tgt, pc + 1))
    B["0branch"] = op_0branch

    def op_ret(m):
        rsp = m.get("rsp", m.t)
        under = rsp < 1
        addr = m.get("rs", m.t, jnp.maximum(rsp - 1, 0))
        m.set("rsp", rsp - 1, m.t)
        set_pc(m, addr)
        raise_exc(m, EXC_STACK, when=under)
        set_status(m, ST_ERR, when=under)
    B["ret"] = op_ret
    B["exit"] = op_ret

    def op_exec(m):
        addr = dpop1(m)
        rsp = m.get("rsp", m.t)
        m.set("rs", cur_pc(m), m.t, jnp.clip(rsp, 0, RS - 1))
        m.set("rsp", rsp + 1, m.t)
        set_pc(m, addr)
        raise_exc(m, EXC_STACK, when=rsp >= RS)
    B["exec"] = op_exec

    def op_doinit(m):
        limit, start_v = dpopn(m, 2)
        fpush(m, limit)
        fpush(m, start_v)
    B["doinit"] = op_doinit

    def op_doloop(m):
        pc = cur_pc(m)
        top_addr = m.get("cs", jnp.clip(pc, 0, CS - 1))
        fsp = m.get("fsp", m.t)
        limit = m.get("fs", m.t, jnp.maximum(fsp - 2, 0))
        ctr = m.get("fs", m.t, jnp.maximum(fsp - 1, 0)) + 1
        done = ctr >= limit
        m.set("fs", ctr, m.t, jnp.maximum(fsp - 1, 0))
        m.set("fsp", fsp + jnp.where(done, -2, 0), m.t)
        set_pc(m, jnp.where(done, pc + 1, top_addr))
    B["doloop"] = op_doloop

    def loop_index(back):
        return lambda m: dpush(
            m, m.get("fs", m.t, jnp.maximum(m.get("fsp", m.t) - back, 0))
        )
    B["i"] = loop_index(1)
    B["j"] = loop_index(3)

    B["unloop"] = lambda m: m.set("fsp", m.get("fsp", m.t) - 2, m.t)
    B["halt"] = lambda m: set_status(m, ST_HALT)
    B["end"] = lambda m: set_status(m, jnp.where(m.t == 0, ST_DONE, ST_FREE))

    def op_dlit(m):
        pc = cur_pc(m)
        dpush(m, m.get("cs", jnp.clip(pc, 0, CS - 1)))
        set_pc(m, pc + 1)
    B["dlit"] = op_dlit

    # io / printing -----------------------------------------------------------

    B["."] = lambda m: out_write(m, OUT_NUM, dpop1(m))
    B["emit"] = lambda m: out_write(m, OUT_CHR, dpop1(m))
    B["cr"] = lambda m: out_write(m, OUT_CHR, 10)

    MAXSTR = 64

    def op_prstr(m):
        pc = cur_pc(m)
        ln = jnp.clip(m.get("cs", jnp.clip(pc, 0, CS - 1)), 0, MAXSTR)
        p = m.get("outp")

        def put(k):
            ok = p + k < OUTN
            m.set("out", OUT_CHR, 2 * (p + k), when=ok)
            m.set("out", m.get("cs", jnp.clip(pc + 1 + k, 0, CS - 1)),
                  2 * (p + k) + 1, when=ok)
        _loop(ln, put)
        m.set("outp", jnp.minimum(p + ln, OUTN))
        set_pc(m, pc + 1 + ln)
    B["prstr"] = op_prstr

    def op_vecprint(m):
        arr = dpop1(m)
        ln = vlen(m, arr)
        p = m.get("outp")

        def put(k):
            ok = p + k < OUTN
            m.set("out", OUT_NUM, 2 * (p + k), when=ok)
            m.set("out", velem(m, arr, k), 2 * (p + k) + 1, when=ok)
        _loop(ln, put)
        m.set("outp", jnp.minimum(p + ln, OUTN))
    B["vecprint"] = op_vecprint

    def make_io_suspend(name):
        opc = isa.opcode[name]

        def op(m):
            # Rewind pc so the host re-inspects the op; args stay on DS.
            set_pc(m, cur_pc(m) - 1)
            m.set("io_op", opc, m.t)
            set_status(m, ST_IOWAIT)
        return op

    for _n in ("out", "in", "send", "receive"):
        B[_n] = make_io_suspend(_n)

    # tasks (non-spawning) ----------------------------------------------------

    B["yield"] = lambda m: set_status(m, ST_YIELD)

    def op_sleep(m):
        ms_v = dpop1(m)
        m.set("timeout", m.get("now") + ms_v, m.t)
        set_status(m, ST_SLEEP)
    B["sleep"] = op_sleep

    def op_await(m):
        ms_v, val, addr = dpopn(m, 3)
        m.set("timeout", m.get("now") + ms_v, m.t)
        m.set("ev_addr", addr, m.t)
        m.set("ev_val", val, m.t)
        set_status(m, ST_EVENT)
    B["await"] = op_await

    B["taskid"] = lambda m: dpush(m, m.t)
    B["ms"] = lambda m: dpush(m, m.get("now"))
    B["steps"] = lambda m: dpush(m, m.get("steps"))

    # exceptions --------------------------------------------------------------

    def op_exception(m):
        handler, exc = dpopn(m, 2)
        m.set("handlers", handler, jnp.clip(exc, 0, NUM_EXC - 1))
    B["exception"] = op_exception

    def op_catch(m):
        dpush(m, m.get("last_exc", m.t))
        m.set("last_exc", 0, m.t)
        m.set("catch_pc", cur_pc(m) - 1, m.t)
        m.set("catch_rsp", m.get("rsp", m.t), m.t)
    B["catch"] = op_catch

    B["throw"] = lambda m: raise_exc(m, jnp.clip(dpop1(m), 1, NUM_EXC - 1))

    # fixed-point DSP scalars (LUT loads from the table operand) --------------

    B["sin"] = un_op_t(lambda v, m: _fpsin_t(v, m).astype(I32))
    B["log"] = un_op_t(lambda v, m: (_fplog10_t(v, m) * 10).astype(I32))
    B["sigmoid"] = un_op_t(lambda v, m: _fpsigmoid_t(v, m).astype(I32))
    B["relu"] = un_op(lambda v: jnp.maximum(v, 0))
    B["sqrt"] = un_op(lambda v: fpsqrt_jnp(v).astype(I32))

    # vector / ANN ops --------------------------------------------------------

    def op_vecload(m):
        src, srcoff, dst = dpopn(m, 3)
        vcompute(m, dst, vlen(m, dst), lambda k: velem(m, src + srcoff, k))
    B["vecload"] = op_vecload

    def op_vecscale(m):
        src, dst, saddr = dpopn(m, 3)
        vcompute(
            m, dst, vlen(m, dst),
            lambda k: vscale(velem(m, src, k), velem(m, saddr, k), True),
        )
    B["vecscale"] = op_vecscale

    def make_eltwise(f):
        def op(m):
            a, b, dst, saddr = dpopn(m, 4)
            vcompute(m, dst, vlen(m, dst), scaled_elem(
                m, saddr, lambda k: f(velem(m, a, k), velem(m, b, k))
            ))
        return op

    B["vecadd"] = make_eltwise(lambda a, b: a + b)
    B["vecmul"] = make_eltwise(lambda a, b: a * b)

    def dot(n, x_of, y_of):
        """sum_{i<n} x_of(i) * y_of(i), int32 wraparound."""
        return lax.fori_loop(
            0, n, lambda i, acc: acc + x_of(i) * y_of(i), I32(0)
        )

    def op_vecfold(m):
        # out[j] = sum_{i<n} in[i] * w[i*m + j] for j < m: the (n x m)
        # weight matrix is read in place, row-major from wgt.
        inv, wgt, outv, saddr = dpopn(m, 4)
        n, mm = vlen(m, inv), vlen(m, outv)
        vcompute(m, outv, mm, scaled_elem(m, saddr, lambda j: dot(
            n, lambda i: velem(m, inv, i), lambda i: velem(m, wgt, i * mm + j)
        )))
    B["vecfold"] = op_vecfold

    def vec_fn(m, fn, v):
        # All five maps, then a select: no branch in the element loop.
        fn = jnp.clip(fn, 0, 4)
        mapped = [
            _fpsigmoid_t(v, m).astype(I32),
            jnp.maximum(v, 0),
            _fpsin_t(v, m).astype(I32),
            (_fplog10_t(v, m) * 10).astype(I32),
            fpsqrt_jnp(v).astype(I32),
        ]
        out = mapped[0]
        for i, r in enumerate(mapped[1:], 1):
            out = jnp.where(fn == i, r, out)
        return out

    def op_vecmap(m):
        src, dst, fn, saddr = dpopn(m, 4)
        vcompute(m, dst, vlen(m, dst), scaled_elem(
            m, saddr, lambda k: vec_fn(m, fn, velem(m, src, k))
        ))
    B["vecmap"] = op_vecmap

    def op_dotprod(m):
        a, b = dpopn(m, 2)
        dpush(m, dot(vlen(m, a), lambda i: velem(m, a, i),
                     lambda i: velem(m, b, i)))
    B["dotprod"] = op_dotprod

    def op_vecmax(m):
        # First index of the maximum over the header length (index 0 for
        # an empty vector), as argmax over the INT_MIN-masked window.
        arr = dpop1(m)
        ln = vlen(m, arr)
        lo = I32(jnp.iinfo(jnp.int32).min)
        first = jnp.where(ln > 0, velem(m, arr, 0), lo)

        def step(k, carry):
            best, idx = carry
            v = velem(m, arr, k)
            return jnp.where(v > best, v, best), jnp.where(v > best, k, idx)
        _, idx = lax.fori_loop(1, ln, step, (first, I32(0)))
        dpush(m, idx)
    B["vecmax"] = op_vecmax

    def make_filter(kind):
        # y_k = y_{k-1} + c*(x_k - y_{k-1})/1000 with y_{-1} = x_0; hull
        # filters |x|, highp writes x - y.
        def op(m):
            arr, off, ln_req, c = dpopn(m, 4)
            base = arr + off
            hdr_ln = mread(m, arr - 1)
            ln = jnp.clip(jnp.minimum(ln_req, hdr_ln - off), 0, MV)

            def x_of(k):
                v = jnp.where(k < ln, velem(m, base, k), 0)
                return jnp.abs(v) if kind == "hull" else v

            def step(k, y):
                y = y + _truncdiv(c * (x_of(k) - y), I32(1000))
                m.set_cell(
                    m.lay.tmp + k, x_of(k) - y if kind == "highp" else y
                )
                return y
            lax.fori_loop(0, ln, step, x_of(0))
            _loop(ln, lambda k: vset(m, base, k, m.cell(m.lay.tmp + k)))
        return op

    B["hull"] = make_filter("hull")
    B["lowp"] = make_filter("lowp")
    B["highp"] = make_filter("highp")

    # -- branch table over the whole opcode space -----------------------------

    num_ops = isa.num_ops
    sup = supported_mask(isa)
    bodies: list[Callable] = []
    for code in range(num_ops):
        nm = isa.name[code]
        if not sup[code]:
            bodies.append(B["nop"])     # declined opcodes bail before dispatch
        elif nm in B:
            bodies.append(B[nm])
        else:
            raise RuntimeError(
                f"opcode {nm!r} claimed by SUPPORTED_WORDS but missing "
                f"from the vmloop branch table"
            )
    bodies.append(B["nop"])   # >= num_ops (FIOS/trap): always bails first

    # One more branch past the opcode space: the stack pre-check's fault.
    bodies.append(lambda m: raise_exc(m, EXC_STACK))
    stack_fault = len(bodies) - 1

    def exec_op(m, opcode):
        code = jnp.clip(opcode, 0, num_ops).astype(I32)
        if not elide_checks:
            # (A verified program skips this: the pre-check is dead.)
            dsp, fsp = m.get("dsp", m.t), m.get("fsp", m.t)
            din, dout = m.tab("din", code), m.tab("dout", code)
            fin, fout = m.tab("fin", code), m.tab("fout", code)
            under = (dsp < din) | (fsp < fin)
            over = (dsp - din + dout > DS) | (fsp - fin + fout > FS)
            code = jnp.where(under | over, stack_fault, code)

        # Bodies run for their effect; some also return a popped value.
        def run(f):
            f(m)
        _tree_switch(code, [lambda f=f: run(f) for f in bodies])

    def fetch(m):
        pc = cur_pc(m)
        pc_ok = (pc >= 0) & (pc < CS)
        instr = m.get("cs", jnp.clip(pc, 0, CS - 1))
        return pc, pc_ok, instr & 3, (instr >> 2).astype(I32)

    def step_instr(m: _Node) -> None:
        # Only the opcode tag needs a branch; the literal, call, bad-tag and
        # bad-pc cases are a few writes each, predicated on their case so
        # the kernel's control flow stays shallow.
        pc, pc_ok, tag, payload = fetch(m)
        is_op = pc_ok & (tag == 0)
        lax.cond(is_op, lambda: (set_pc(m, pc + 1), exec_op(m, payload))[1],
                 lambda: None)

        dsp, rsp = m.get("dsp", m.t), m.get("rsp", m.t)
        is_lit = pc_ok & (tag == 1)
        is_call = pc_ok & (tag == 2)
        lit_ok = is_lit if elide_checks else is_lit & (dsp < DS)
        call_ok = is_call & (rsp < RS)
        new_pc = jnp.where(call_ok, payload, pc + 1)
        set_pc(m, new_pc, when=~is_op & pc_ok & ~(is_call & ~call_ok))
        m.set("ds", payload, m.t, jnp.clip(dsp, 0, DS - 1), when=lit_ok)
        m.set("dsp", dsp + 1, m.t, when=lit_ok)
        m.set("rs", pc + 1, m.t, jnp.clip(rsp, 0, RS - 1), when=call_ok)
        m.set("rsp", rsp + 1, m.t, when=call_ok)
        raise_exc(m, EXC_STACK, when=(is_lit & ~lit_ok) | (is_call & ~call_ok))
        raise_exc(m, EXC_TRAP, when=(pc_ok & (tag == 3)) | ~pc_ok)
        set_status(m, ST_ERR, when=~pc_ok)
        m.set("steps", m.get("steps") + 1)

        # Exception dispatch (identical to interp.step_instr), predicated.
        exc = m.get("pending_exc", m.t) > 0
        code = jnp.clip(m.get("pending_exc", m.t), 0, NUM_EXC - 1)
        handler = m.get("handlers", code)
        has = exc & (handler > 0)
        crsp = jnp.clip(m.get("catch_rsp", m.t), 0, RS - 1)
        m.set("rs", m.get("catch_pc", m.t), m.t, crsp, when=has)
        m.set("rsp", crsp + 1, m.t, when=has)
        m.set("last_exc", code, m.t, when=exc)
        m.set("pending_exc", 0, m.t, when=exc)
        set_pc(m, handler, when=has)
        set_status(m, ST_ERR, when=exc & ~has)

    def instr_supported(m: _Node):
        """True iff the *next* instruction may execute in-kernel.  Non-OP
        tags and invalid pcs are always supported (they are the exact trap/
        literal/call semantics of the lax interpreter); OP tags consult the
        claim mask — index ``num_ops`` (FIOS and out-of-table traps) is
        False, so those bail to the host path."""
        _, pc_ok, tag, payload = fetch(m)
        op_ok = m.tab("sup", jnp.clip(payload, 0, num_ops)) != 0
        return jnp.where(pc_ok & (tag == 0), op_ok, True)

    def retire_bin(m: _Node):
        """The obs histogram bin of the next instruction (the
        ``repro.obs.metrics`` layout: opcodes, fios/trap, lit, call,
        invalid)."""
        _, pc_ok, tag, payload = fetch(m)
        b = jnp.where(tag == 0, jnp.clip(payload, 0, num_ops), num_ops + tag)
        return jnp.where(pc_ok, b, num_ops + 3).astype(I32)

    return CoreStep(step_instr, instr_supported, retire_bin, fetch)


def make_run_core(
    cfg: VMConfig,
    isa: ISA | None = None,
    obs: bool = False,
    elide_checks: bool = False,
):
    """Returns ``run_core(row, tab, steps)``: the fetch/dispatch/execute
    loop of Alg. 1 over one node's row ref ``(1, width)`` (see
    :class:`Layout`) and the flat table ref, restricted to the claimed
    opcode set.  Stops on slice exhaustion, a status change (suspend/halt/
    error), or the first unclaimed opcode — in the last case *before*
    executing it, so the host-side lax interpreter resumes from identical
    state.  It writes its results into the row: ``n_exec``, ``bailed``
    (0/1) and ``bail_op``, the opcode that caused the bail (clipped to
    ``num_ops`` for FIOS/trap) or -1 when the loop did not bail — the raw
    feed for the per-opcode bail histogram.

    With ``obs=True`` the loop also counts retirements into the row's
    ``(num_ops + 4,)`` histogram (the ``repro.obs.metrics`` bin layout:
    ISA opcodes, then fios/trap, lit, call, invalid).  Only *retired* steps
    are binned — the bailing instruction is not (the lax tail retires and
    counts it), so kernel + tail histograms always sum to exactly what a
    pure-lax slice would count."""
    isa = isa or get_isa()
    num_ops = isa.num_ops
    lay = Layout(cfg, isa)
    _, spans = flat_tables(isa)
    core_step = make_core_step(cfg, isa, elide_checks)

    def run_core(row, tab, steps):
        m = _Node(lay, row, tab, spans)

        # The carry holds the task's status and whether the next
        # instruction is claimed: a while_loop's cond may not read refs
        # (interpret mode would see stale values).
        def status():
            return m.get("tstatus", m.t)

        def supported():
            return core_step.supported(m).astype(I32)

        def cond(carry):
            n, ok, st = carry
            return (n < steps) & (st == ST_RUN) & (ok != 0)

        def body(carry):
            n, _, _ = carry
            if obs:
                b = lay.hist + core_step.retire_bin(m)
                m.set_cell(b, m.cell(b) + 1)
            core_step.step(m)
            return n + 1, supported(), status()

        n, ok, st = lax.while_loop(cond, body, (I32(0), supported(), status()))
        # The loop bails when it stopped only because the next instruction
        # is declined.
        bailed = ((ok == 0) & (n < steps) & (st == ST_RUN)).astype(I32)
        # bailed implies pc_ok & tag == 0 (instr_supported is True for
        # every other shape), so the payload at pc is the declined opcode.
        _, _, _, payload = core_step.fetch(m)
        m.set_cell(lay.n_exec, n)
        m.set_cell(lay.bailed, bailed)
        m.set_cell(
            lay.bail_op, jnp.where(bailed != 0, jnp.clip(payload, 0, num_ops), -1)
        )

    return run_core

