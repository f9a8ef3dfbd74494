"""Jitted bytecode interpreter — paper §3.10 (Alg. 1) + §6.4 (Alg. 6).

The decoder is a ``lax.switch`` over consecutively numbered opcodes — the
XLA analogue of the paper's branch look-up table, giving (near-)constant
dispatch time.  ``vmloop(state, steps)`` executes at most ``steps``
instructions of the *current task* and returns as soon as the task suspends
(IO wait / sleep / event / yield / end) — the paper's micro-slicing that
embeds the VM in a host IO service loop (Fig. 10).

``schedule`` is the multi-tasking selector of Alg. 6 (IO events highest
priority, then timeouts, then ready tasks), operating on the packed per-task
status vector instead of the paper's 2-bit mask (same semantics, testable
against the Python oracle).

Everything here is pure JAX; the only host interaction is servicing FIOS
calls between loop rounds (see ``repro.core.vm.machine``).  The functional
slice form ``run_slice_fn`` composes under ``vmap``: the fleet runtime
(``repro.core.vm.fleet``) maps it over a node axis to run N VMs —
sensor-network nodes or voting replicas — in one device program.

NOTE: the Pallas vmloop kernel (``repro.kernels.vmloop.ref``) carries an
independent transliteration of this step semantics (as ``oracle.py`` does
in plain Python) — a semantic change to any op body, the stack pre-check,
or the exception dispatch below must be mirrored there;
tests/test_vm_pallas.py is the byte-exactness tripwire.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import VMConfig
from repro.core.fixedpoint import (
    fplog10_jnp,
    fpsigmoid_jnp,
    fpsin_jnp,
    fpsqrt_jnp,
)
from repro.core.vm.spec import (
    EXC_BOUNDS,
    EXC_DIVBYZERO,
    EXC_STACK,
    EXC_TRAP,
    FIOS_BASE,
    ISA,
    MEM_BASE,
    NUM_EXC,
    STACK_EFFECTS,
    ST_DONE,
    ST_ERR,
    ST_EVENT,
    ST_FREE,
    ST_HALT,
    ST_IOWAIT,
    ST_RUN,
    ST_SLEEP,
    ST_YIELD,
    TAG_CALL,
    TAG_LIT,
    TAG_OP,
    get_isa,
)
from repro.core.vm.vmstate import OUT_CHR, OUT_NUM, VMState

I32 = jnp.int32

# ---------------------------------------------------------------------------
# Static stack-effect table: (ds_in, ds_out, fs_in, fs_out) per word.
# The pre-check before dispatch raises EXC_STACK — the paper's "enhanced
# error detection" at the architecture level.
# Declared once per Word in spec.STACK_EFFECTS; re-exported here under the
# historical name (the oracle, the Pallas kernel's make_tables and the
# static verifier all read the same declaration).
# ---------------------------------------------------------------------------

STACK_NEEDS: dict[str, tuple[int, int, int, int]] = dict(STACK_EFFECTS)


def _truncdiv(a, b):
    """C-style truncation-toward-zero division (paper target is C)."""
    q = jnp.abs(a) // jnp.maximum(jnp.abs(b), 1)
    return jnp.where((a < 0) ^ (b < 0), -q, q).astype(I32)


def _truncmod(a, b):
    return (a - _truncdiv(a, b) * b).astype(I32)


def _muldiv(a, b, c):
    """64-bit-exact a*b/c on 32-bit lanes (the paper's double-word scaled op).

    Unsigned 32x32->64 multiply via 16-bit limbs, then 64/32 restoring
    division, all in uint32 — no x64 mode required.
    """
    u32 = jnp.uint32
    sign = ((a < 0) ^ (b < 0)) ^ (c < 0)
    A = jnp.abs(a).astype(u32)
    B = jnp.abs(b).astype(u32)
    C = jnp.maximum(jnp.abs(c), 1).astype(u32)
    al, ah = A & u32(0xFFFF), A >> 16
    bl, bh = B & u32(0xFFFF), B >> 16
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    hh = ah * bh
    mid = lh + hl
    mid_carry = (mid < lh).astype(u32)
    lo = ll + (mid << 16)
    lo_carry = (lo < ll).astype(u32)
    hi = hh + (mid >> 16) + (mid_carry << 16) + lo_carry

    def div_step(k, carry):
        hi, lo, rem, q = carry
        bit = (hi >> 31) & u32(1)
        hi = (hi << 1) | (lo >> 31)
        lo = lo << 1
        rem = (rem << 1) | bit
        ge = rem >= C
        rem = jnp.where(ge, rem - C, rem)
        q = (q << 1) | ge.astype(u32)
        return hi, lo, rem, q

    _, _, _, q = lax.fori_loop(
        0, 64, div_step, (hi, lo, u32(0), u32(0))
    )
    qi = q.astype(I32)
    return jnp.where(sign, -qi, qi)


# ---------------------------------------------------------------------------
# Interpreter factory: all shapes/sizes are static per VMConfig.
# ---------------------------------------------------------------------------

class Interpreter:
    """Builds jitted vmloop/schedule for one (ISA, VMConfig) pair."""

    def __init__(
        self, cfg: VMConfig, isa: ISA | None = None, elide_checks: bool = False
    ):
        self.cfg = cfg
        self.isa = isa or get_isa()
        # ``elide_checks=True`` drops the per-step stack pre-check (the
        # LUT-driven under/overflow test before dispatch and the TAG_LIT
        # push-overflow test) at build time.  Only sound for programs the
        # static verifier (repro.analysis) proved EXC_STACK-free: every
        # body-internal check (pick bounds, ret/call RS checks, div-by-zero,
        # address bounds) stays, so behaviour is byte-identical on verified
        # programs and undefined only where the verifier already rejected.
        self.elide_checks = bool(elide_checks)
        self._build()
        self.vmloop = jax.jit(self._vmloop, static_argnames=("steps",))
        self.schedule = jax.jit(self._schedule)
        self.run_slice = jax.jit(self._run_slice, static_argnames=("steps",))

    # -- low-level state helpers (all take/return VMState) --------------------

    def _build(self):
        cfg, isa = self.cfg, self.isa
        CS, MEM = cfg.cs_size, cfg.mem_size
        DS, RS, FS = cfg.ds_size, cfg.rs_size, cfg.fs_size
        MV = cfg.max_vec
        OUTN = cfg.out_ring_size

        def dpeek(st, k=1):
            t = st.cur
            return st.ds[t, jnp.maximum(st.dsp[t] - k, 0)]

        def dpop1(st):
            t = st.cur
            v = st.ds[t, jnp.maximum(st.dsp[t] - 1, 0)]
            return st._replace(dsp=st.dsp.at[t].add(-1)), v

        def dpopn(st, n):
            t = st.cur
            vals = tuple(
                st.ds[t, jnp.maximum(st.dsp[t] - n + k, 0)] for k in range(n)
            )
            return st._replace(dsp=st.dsp.at[t].add(-n)), vals

        def dpush(st, v):
            t = st.cur
            return st._replace(
                ds=st.ds.at[t, jnp.clip(st.dsp[t], 0, DS - 1)].set(v.astype(I32) if hasattr(v, "astype") else I32(v)),
                dsp=st.dsp.at[t].add(1),
            )

        def fpush(st, v):
            t = st.cur
            return st._replace(
                fs=st.fs.at[t, jnp.clip(st.fsp[t], 0, FS - 1)].set(v),
                fsp=st.fsp.at[t].add(1),
            )

        def set_pc(st, pc):
            return st._replace(pc=st.pc.at[st.cur].set(pc.astype(I32)))

        def cur_pc(st):
            return st.pc[st.cur]

        def raise_exc(st, code):
            t = st.cur
            return st._replace(
                pending_exc=st.pending_exc.at[t].set(
                    jnp.where(st.pending_exc[t] == 0, code, st.pending_exc[t])
                )
            )

        def set_status(st, s):
            return st._replace(tstatus=st.tstatus.at[st.cur].set(s))

        # unified CS/MEM addressing -----------------------------------------

        def addr_valid(addr):
            in_cs = (addr >= 0) & (addr < CS)
            in_mem = (addr >= MEM_BASE) & (addr < MEM_BASE + MEM)
            return in_cs | in_mem

        def mread(st, addr):
            in_mem = addr >= MEM_BASE
            cs_v = st.cs[jnp.clip(addr, 0, CS - 1)]
            mem_v = st.mem[jnp.clip(addr - MEM_BASE, 0, MEM - 1)]
            return jnp.where(in_mem, mem_v, cs_v)

        def mwrite(st, addr, v):
            v = v.astype(I32)
            in_mem = addr >= MEM_BASE
            cs_idx = jnp.where(in_mem, CS, jnp.clip(addr, 0, CS - 1))
            mem_idx = jnp.where(in_mem, jnp.clip(addr - MEM_BASE, 0, MEM - 1), MEM)
            return st._replace(
                cs=st.cs.at[cs_idx].set(v, mode="drop"),
                mem=st.mem.at[mem_idx].set(v, mode="drop"),
            )

        def vread(st, addr, window, length=None):
            """Gather ``window`` cells from addr; mask beyond header length."""
            ln = mread(st, addr - 1) if length is None else length
            ln = jnp.clip(ln, 0, window)
            idx = addr + jnp.arange(window, dtype=I32)
            in_mem = addr >= MEM_BASE
            cs_vals = jnp.take(st.cs, jnp.clip(idx, 0, CS - 1))
            mem_vals = jnp.take(st.mem, jnp.clip(idx - MEM_BASE, 0, MEM - 1))
            vals = jnp.where(in_mem, mem_vals, cs_vals)
            mask = jnp.arange(window) < ln
            return jnp.where(mask, vals, 0), ln, mask

        def vwrite(st, addr, vals, ln):
            window = vals.shape[0]
            mask = jnp.arange(window) < ln
            in_mem = addr >= MEM_BASE
            idx = addr + jnp.arange(window, dtype=I32)
            cs_idx = jnp.where(mask & ~in_mem, jnp.clip(idx, 0, CS - 1), CS)
            mem_idx = jnp.where(mask & in_mem, jnp.clip(idx - MEM_BASE, 0, MEM - 1), MEM)
            return st._replace(
                cs=st.cs.at[cs_idx].set(vals.astype(I32), mode="drop"),
                mem=st.mem.at[mem_idx].set(vals.astype(I32), mode="drop"),
            )

        def out_write(st, kind, val):
            p = st.outp
            ok = p < OUTN
            idx0 = jnp.where(ok, 2 * p, 2 * OUTN)
            return st._replace(
                out=st.out.at[idx0].set(kind, mode="drop")
                .at[idx0 + 1].set(val.astype(I32), mode="drop"),
                outp=jnp.where(ok, p + 1, p),
            )

        def out_write_vec(st, vals, ln):
            window = vals.shape[0]
            p = st.outp
            k = jnp.arange(window, dtype=I32)
            mask = (k < ln) & (p + k < OUTN)
            base = 2 * (p + k)
            kidx = jnp.where(mask, base, 2 * OUTN)
            vidx = jnp.where(mask, base + 1, 2 * OUTN)
            out = st.out.at[kidx].set(OUT_NUM, mode="drop")
            out = out.at[vidx].set(vals.astype(I32), mode="drop")
            return st._replace(out=out, outp=jnp.minimum(p + jnp.clip(ln, 0, window), OUTN))

        # scale-vector application (paper Tab. 5 semantics) ------------------

        def vscale(vals, svals, s_on):
            expanded = vals * jnp.where(svals > 0, svals, 1)
            divisor = jnp.where(svals < 0, -svals, 1)
            reduced = jnp.sign(vals) * (jnp.abs(vals) // divisor)
            scaled = jnp.where(svals > 0, expanded, jnp.where(svals < 0, reduced, vals))
            return jnp.where(s_on, scaled, vals)

        def apply_scalevec(st, dst_vals, ln, saddr):
            s_on = saddr != 0
            svals, _, _ = vread(st, jnp.where(s_on, saddr, I32(1)), MV, length=ln)
            return vscale(dst_vals, svals, s_on)

        # -- opcode implementations ------------------------------------------

        def bin_op(f):
            def op(st):
                st, (a, b) = dpopn(st, 2)
                return dpush(st, f(a, b))
            return op

        def un_op(f):
            def op(st):
                st, v = dpop1(st)
                return dpush(st, f(v))
            return op

        def cmp_op(f):
            return bin_op(lambda a, b: jnp.where(f(a, b), I32(-1), I32(0)))

        B = {}

        B["nop"] = lambda st: st
        B["dup"] = lambda st: dpush(st, dpeek(st))

        def op_drop(st):
            st, _ = dpop1(st)
            return st
        B["drop"] = op_drop

        def op_swap(st):
            st, (a, b) = dpopn(st, 2)
            return dpush(dpush(st, b), a)
        B["swap"] = op_swap

        def op_over(st):
            return dpush(st, dpeek(st, 2))
        B["over"] = op_over

        def op_rot(st):
            st, (a, b, c) = dpopn(st, 3)
            return dpush(dpush(dpush(st, b), c), a)
        B["rot"] = op_rot

        def op_nip(st):
            st, (a, b) = dpopn(st, 2)
            return dpush(st, b)
        B["nip"] = op_nip

        def op_tuck(st):
            st, (a, b) = dpopn(st, 2)
            return dpush(dpush(dpush(st, b), a), b)
        B["tuck"] = op_tuck

        def op_pick(st):
            st, n = dpop1(st)
            t = st.cur
            idx = jnp.clip(st.dsp[t] - 1 - n, 0, DS - 1)
            bad = (n < 0) | (n >= st.dsp[t])
            st = dpush(st, st.ds[t, idx])
            return lax.cond(bad, lambda s: raise_exc(s, EXC_STACK), lambda s: s, st)
        B["pick"] = op_pick

        def op_2dup(st):
            a, b = dpeek(st, 2), dpeek(st, 1)
            return dpush(dpush(st, a), b)
        B["2dup"] = op_2dup

        def op_2drop(st):
            st, _ = dpopn(st, 2)
            return st
        B["2drop"] = op_2drop

        B["depth"] = lambda st: dpush(st, st.dsp[st.cur])

        B["+"] = bin_op(lambda a, b: a + b)
        B["-"] = bin_op(lambda a, b: a - b)
        B["*"] = bin_op(lambda a, b: a * b)

        def op_div(st):
            st, (a, b) = dpopn(st, 2)
            st = dpush(st, _truncdiv(a, b))
            return lax.cond(b == 0, lambda s: raise_exc(s, EXC_DIVBYZERO), lambda s: s, st)
        B["/"] = op_div

        def op_mod(st):
            st, (a, b) = dpopn(st, 2)
            st = dpush(st, _truncmod(a, b))
            return lax.cond(b == 0, lambda s: raise_exc(s, EXC_DIVBYZERO), lambda s: s, st)
        B["mod"] = op_mod

        def op_muldiv(st):
            st, (a, b, c) = dpopn(st, 3)
            st = dpush(st, _muldiv(a, b, c))
            return lax.cond(c == 0, lambda s: raise_exc(s, EXC_DIVBYZERO), lambda s: s, st)
        B["*/"] = op_muldiv

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

        def op_fetch(st):
            st, addr = dpop1(st)
            st = dpush(st, mread(st, addr))
            return lax.cond(addr_valid(addr), lambda s: s, lambda s: raise_exc(s, EXC_BOUNDS), st)
        B["@"] = op_fetch

        def op_store(st):
            st, (v, addr) = dpopn(st, 2)
            st = mwrite(st, addr, v)
            return lax.cond(addr_valid(addr), lambda s: s, lambda s: raise_exc(s, EXC_BOUNDS), st)
        B["!"] = op_store

        def op_addstore(st):
            st, (v, addr) = dpopn(st, 2)
            st = mwrite(st, addr, mread(st, addr) + v)
            return lax.cond(addr_valid(addr), lambda s: s, lambda s: raise_exc(s, EXC_BOUNDS), st)
        B["+!"] = op_addstore

        def op_get(st):
            st, (n, arr) = dpopn(st, 2)
            ln = mread(st, arr - 1)
            bad = (n < 0) | (n >= ln)
            st = dpush(st, mread(st, arr + jnp.clip(n, 0, jnp.maximum(ln - 1, 0))))
            return lax.cond(bad, lambda s: raise_exc(s, EXC_BOUNDS), lambda s: s, st)
        B["get"] = op_get

        def op_put(st):
            st, (v, n, arr) = dpopn(st, 3)
            ln = mread(st, arr - 1)
            bad = (n < 0) | (n >= ln)
            st = lax.cond(
                bad, lambda s: s, lambda s: mwrite(s, arr + n, v), st
            )
            return lax.cond(bad, lambda s: raise_exc(s, EXC_BOUNDS), lambda s: s, st)
        B["put"] = op_put

        def op_push(st):
            # softcore stack (paper §3.2): arr[0] is top pointer.
            st, (v, arr) = dpopn(st, 2)
            top = mread(st, arr)
            ln = mread(st, arr - 1)
            bad = top + 1 >= ln
            def do(s):
                s = mwrite(s, arr + top + 1, v)
                return mwrite(s, arr, top + 1)
            st = lax.cond(bad, lambda s: raise_exc(s, EXC_BOUNDS), do, st)
            return st
        B["push"] = op_push

        def op_pop(st):
            st, arr = dpop1(st)
            top = mread(st, arr)
            bad = top <= 0
            v = mread(st, arr + jnp.maximum(top, 1))
            st = dpush(st, jnp.where(bad, 0, v))
            st = lax.cond(
                bad,
                lambda s: raise_exc(s, EXC_BOUNDS),
                lambda s: mwrite(s, arr, top - 1),
                st,
            )
            return st
        B["pop"] = op_pop

        def op_fill(st):
            st, (v, arr) = dpopn(st, 2)
            _, ln, _ = vread(st, arr, MV)
            return vwrite(st, arr, jnp.full((MV,), 0, I32) + v, ln)
        B["fill"] = op_fill

        def op_len(st):
            st, arr = dpop1(st)
            return dpush(st, mread(st, arr - 1))
        B["len"] = op_len

        # control ----------------------------------------------------------

        def op_branch(st):
            tgt = st.cs[jnp.clip(cur_pc(st), 0, CS - 1)]
            return set_pc(st, tgt)
        B["branch"] = op_branch

        def op_0branch(st):
            st, f = dpop1(st)
            pc = cur_pc(st)
            tgt = st.cs[jnp.clip(pc, 0, CS - 1)]
            return set_pc(st, jnp.where(f == 0, tgt, pc + 1))
        B["0branch"] = op_0branch

        def op_ret(st):
            t = st.cur
            under = st.rsp[t] < 1
            addr = st.rs[t, jnp.maximum(st.rsp[t] - 1, 0)]
            st = st._replace(rsp=st.rsp.at[t].add(-1))
            st = set_pc(st, addr)
            return lax.cond(under, lambda s: set_status(raise_exc(s, EXC_STACK), ST_ERR), lambda s: s, st)
        B["ret"] = op_ret
        B["exit"] = op_ret

        def op_exec(st):
            st, addr = dpop1(st)
            t = st.cur
            over = st.rsp[t] >= RS
            st = st._replace(
                rs=st.rs.at[t, jnp.clip(st.rsp[t], 0, RS - 1)].set(cur_pc(st)),
                rsp=st.rsp.at[t].add(1),
            )
            st = set_pc(st, addr)
            return lax.cond(over, lambda s: raise_exc(s, EXC_STACK), lambda s: s, st)
        B["exec"] = op_exec

        def op_doinit(st):
            st, (limit, start_v) = dpopn(st, 2)
            return fpush(fpush(st, limit), start_v)
        B["doinit"] = op_doinit

        def op_doloop(st):
            t = st.cur
            pc = cur_pc(st)
            top_addr = st.cs[jnp.clip(pc, 0, CS - 1)]
            limit = st.fs[t, jnp.maximum(st.fsp[t] - 2, 0)]
            ctr = st.fs[t, jnp.maximum(st.fsp[t] - 1, 0)] + 1
            done = ctr >= limit
            st = st._replace(
                fs=st.fs.at[t, jnp.maximum(st.fsp[t] - 1, 0)].set(ctr),
                fsp=st.fsp.at[t].add(jnp.where(done, -2, 0)),
            )
            return set_pc(st, jnp.where(done, pc + 1, top_addr))
        B["doloop"] = op_doloop

        B["i"] = lambda st: dpush(st, st.fs[st.cur, jnp.maximum(st.fsp[st.cur] - 1, 0)])
        B["j"] = lambda st: dpush(st, st.fs[st.cur, jnp.maximum(st.fsp[st.cur] - 3, 0)])

        def op_unloop(st):
            return st._replace(fsp=st.fsp.at[st.cur].add(-2))
        B["unloop"] = op_unloop

        B["halt"] = lambda st: set_status(st, ST_HALT)

        def op_end(st):
            # Task 0 finishing the frame -> DONE; spawned task -> slot freed.
            s = jnp.where(st.cur == 0, ST_DONE, ST_FREE)
            return set_status(st, s)
        B["end"] = op_end

        def op_dlit(st):
            pc = cur_pc(st)
            v = st.cs[jnp.clip(pc, 0, CS - 1)]
            return set_pc(dpush(st, v), pc + 1)
        B["dlit"] = op_dlit

        # io / printing ------------------------------------------------------

        def op_print(st):
            st, v = dpop1(st)
            return out_write(st, OUT_NUM, v)
        B["."] = op_print

        def op_emit(st):
            st, v = dpop1(st)
            return out_write(st, OUT_CHR, v)
        B["emit"] = op_emit

        B["cr"] = lambda st: out_write(st, OUT_CHR, I32(10))

        MAXSTR = 64

        def op_prstr(st):
            pc = cur_pc(st)
            ln = jnp.clip(st.cs[jnp.clip(pc, 0, CS - 1)], 0, MAXSTR)
            idx = pc + 1 + jnp.arange(MAXSTR, dtype=I32)
            chars = jnp.take(st.cs, jnp.clip(idx, 0, CS - 1))
            p = st.outp
            k = jnp.arange(MAXSTR, dtype=I32)
            mask = (k < ln) & (p + k < OUTN)
            base = 2 * (p + k)
            out = st.out.at[jnp.where(mask, base, 2 * OUTN)].set(OUT_CHR, mode="drop")
            out = out.at[jnp.where(mask, base + 1, 2 * OUTN)].set(chars, mode="drop")
            st = st._replace(out=out, outp=jnp.minimum(p + ln, OUTN))
            # Compiler enforces string length <= MAXSTR, so ln is exact.
            return set_pc(st, pc + 1 + ln)
        B["prstr"] = op_prstr

        def op_vecprint(st):
            st, arr = dpop1(st)
            vals, ln, _ = vread(st, arr, MV)
            return out_write_vec(st, vals, ln)
        B["vecprint"] = op_vecprint

        def make_io_suspend(name):
            opc = isa.opcode[name]
            def op(st):
                # Rewind pc so host re-inspects the op; args stay on DS.
                st = set_pc(st, cur_pc(st) - 1)
                st = st._replace(io_op=st.io_op.at[st.cur].set(opc))
                return set_status(st, ST_IOWAIT)
            return op

        for _n in ("out", "in", "send", "receive"):
            B[_n] = make_io_suspend(_n)

        # tasks ---------------------------------------------------------------

        B["yield"] = lambda st: set_status(st, ST_YIELD)

        def op_sleep(st):
            st, ms_v = dpop1(st)
            t = st.cur
            st = st._replace(timeout=st.timeout.at[t].set(st.now + ms_v))
            return set_status(st, ST_SLEEP)
        B["sleep"] = op_sleep

        def op_await(st):
            st, (ms_v, val, addr) = dpopn(st, 3)
            t = st.cur
            st = st._replace(
                timeout=st.timeout.at[t].set(st.now + ms_v),
                ev_addr=st.ev_addr.at[t].set(addr),
                ev_val=st.ev_val.at[t].set(val),
            )
            return set_status(st, ST_EVENT)
        B["await"] = op_await

        def op_task(st):
            st, (prio, deadline, addr) = dpopn(st, 3)
            free = st.tstatus == ST_FREE
            slot = jnp.argmax(free).astype(I32)
            found = free[slot]
            def spawn(s):
                s = s._replace(
                    pc=s.pc.at[slot].set(addr),
                    dsp=s.dsp.at[slot].set(0),
                    # Return address 0 = canonical `end` cell: when the task's
                    # entry word returns, the task terminates cleanly.
                    rs=s.rs.at[slot, 0].set(0),
                    rsp=s.rsp.at[slot].set(1),
                    fsp=s.fsp.at[slot].set(0),
                    tstatus=s.tstatus.at[slot].set(ST_YIELD),
                    prio=s.prio.at[slot].set(prio),
                    deadline=s.deadline.at[slot].set(deadline),
                    catch_pc=s.catch_pc.at[slot].set(0),
                    catch_rsp=s.catch_rsp.at[slot].set(0),
                    pending_exc=s.pending_exc.at[slot].set(0),
                    last_exc=s.last_exc.at[slot].set(0),
                    io_op=s.io_op.at[slot].set(0),
                )
                return dpush(s, slot)
            return lax.cond(found, spawn, lambda s: dpush(s, I32(-1)), st)
        B["task"] = op_task

        B["taskid"] = lambda st: dpush(st, st.cur)
        B["ms"] = lambda st: dpush(st, st.now)
        B["steps"] = lambda st: dpush(st, st.steps)

        # exceptions ------------------------------------------------------------

        def op_exception(st):
            st, (handler, exc) = dpopn(st, 2)
            idx = jnp.clip(exc, 0, NUM_EXC - 1)
            return st._replace(handlers=st.handlers.at[idx].set(handler))
        B["exception"] = op_exception

        def op_catch(st):
            # The catch point is the `catch` instruction itself: when a
            # handler returns, `catch` re-executes and pushes the exception
            # code (paper Def. 3 / §3.8).
            t = st.cur
            st = dpush(st, st.last_exc[t])
            return st._replace(
                last_exc=st.last_exc.at[t].set(0),
                catch_pc=st.catch_pc.at[t].set(cur_pc(st) - 1),
                catch_rsp=st.catch_rsp.at[t].set(st.rsp[t]),
            )
        B["catch"] = op_catch

        def op_throw(st):
            st, exc = dpop1(st)
            return raise_exc(st, jnp.clip(exc, 1, NUM_EXC - 1))
        B["throw"] = op_throw

        # fixed-point DSP scalars -------------------------------------------------

        B["sin"] = un_op(lambda v: fpsin_jnp(v).astype(I32))
        B["log"] = un_op(lambda v: (fplog10_jnp(v) * 10).astype(I32))
        B["sigmoid"] = un_op(lambda v: fpsigmoid_jnp(v).astype(I32))
        B["relu"] = un_op(lambda v: jnp.maximum(v, 0))
        B["sqrt"] = un_op(lambda v: fpsqrt_jnp(v).astype(I32))

        def op_rnd(st):
            st, n = dpop1(st)
            rng = st.rng * jnp.uint32(1664525) + jnp.uint32(1013904223)
            r = (rng >> 16).astype(I32)
            v = jnp.where(n > 0, r % jnp.maximum(n, 1), 0)
            return dpush(st._replace(rng=rng), v)
        B["rnd"] = op_rnd

        # vector / ANN ops ----------------------------------------------------------

        def op_vecload(st):
            st, (src, srcoff, dst) = dpopn(st, 3)
            _, ln, _ = vread(st, dst, MV)
            vals, _, _ = vread(st, src + srcoff, MV, length=ln)
            return vwrite(st, dst, vals, ln)
        B["vecload"] = op_vecload

        def op_vecscale(st):
            st, (src, dst, saddr) = dpopn(st, 3)
            _, ln, _ = vread(st, dst, MV)
            vals, _, _ = vread(st, src, MV, length=ln)
            svals, _, _ = vread(st, saddr, MV, length=ln)
            return vwrite(st, dst, vscale(vals, svals, jnp.bool_(True)), ln)
        B["vecscale"] = op_vecscale

        def make_eltwise(f):
            def op(st):
                st, (a, b, dst, saddr) = dpopn(st, 4)
                _, ln, _ = vread(st, dst, MV)
                av, _, _ = vread(st, a, MV, length=ln)
                bv, _, _ = vread(st, b, MV, length=ln)
                r = f(av, bv)
                r = apply_scalevec(st, r, ln, saddr)
                return vwrite(st, dst, r, ln)
            return op

        B["vecadd"] = make_eltwise(lambda a, b: a + b)
        B["vecmul"] = make_eltwise(lambda a, b: a * b)

        def op_vecfold(st):
            st, (inv, wgt, outv, saddr) = dpopn(st, 4)
            iv, n, imask = vread(st, inv, MV)
            _, m, _ = vread(st, outv, MV)
            # Gather the (n x m) weight matrix from the flat wgt array.
            ii = jnp.arange(MV, dtype=I32)[:, None]
            jj = jnp.arange(MV, dtype=I32)[None, :]
            flat_idx = wgt + ii * m + jj
            in_mem = wgt >= MEM_BASE
            cs_w = jnp.take(st.cs, jnp.clip(flat_idx, 0, CS - 1))
            mem_w = jnp.take(st.mem, jnp.clip(flat_idx - MEM_BASE, 0, MEM - 1))
            w = jnp.where(in_mem, mem_w, cs_w)
            wmask = (ii < n) & (jj < m)
            w = jnp.where(wmask, w, 0)
            acc = jnp.sum(iv[:, None] * w, axis=0).astype(I32)   # int32 accumulate
            acc = apply_scalevec(st, acc, m, saddr)
            return vwrite(st, outv, acc, m)
        B["vecfold"] = op_vecfold

        def op_vecmap(st):
            st, (src, dst, fn, saddr) = dpopn(st, 4)
            _, ln, _ = vread(st, dst, MV)
            vals, _, _ = vread(st, src, MV, length=ln)
            mapped = lax.switch(
                jnp.clip(fn, 0, 4),
                [
                    lambda v: fpsigmoid_jnp(v).astype(I32),
                    lambda v: jnp.maximum(v, 0),
                    lambda v: fpsin_jnp(v).astype(I32),
                    lambda v: (fplog10_jnp(v) * 10).astype(I32),
                    lambda v: fpsqrt_jnp(v).astype(I32),
                ],
                vals,
            )
            mapped = apply_scalevec(st, mapped, ln, saddr)
            return vwrite(st, dst, mapped, ln)
        B["vecmap"] = op_vecmap

        def op_dotprod(st):
            st, (a, b) = dpopn(st, 2)
            av, n, _ = vread(st, a, MV)
            bv, _, _ = vread(st, b, MV, length=n)
            return dpush(st, jnp.sum(av * bv).astype(I32))
        B["dotprod"] = op_dotprod

        def op_vecmax(st):
            st, arr = dpop1(st)
            vals, ln, mask = vread(st, arr, MV)
            vals = jnp.where(mask, vals, jnp.iinfo(jnp.int32).min)
            return dpush(st, jnp.argmax(vals).astype(I32))
        B["vecmax"] = op_vecmax

        def iir_lowpass(vals, ln, k):
            """y_i = y_{i-1} + k*(x_i - y_{i-1})/1000, y_{-1} = x_0."""
            def step(y, xm):
                x, m = xm
                y2 = y + _truncdiv(k * (x - y), I32(1000))
                y2 = jnp.where(m, y2, y)
                return y2, y2
            mask = jnp.arange(MV) < ln
            y0 = vals[0]
            _, ys = lax.scan(step, y0, (vals, mask))
            return ys

        def make_filter(kind):
            def op(st):
                st, (arr, off, ln_req, k) = dpopn(st, 4)
                base = arr + off
                hdr_ln = mread(st, arr - 1)
                ln = jnp.clip(jnp.minimum(ln_req, hdr_ln - off), 0, MV)
                vals, _, _ = vread(st, base, MV, length=ln)
                if kind == "hull":
                    x = jnp.abs(vals)
                    y = iir_lowpass(x, ln, k)
                elif kind == "lowp":
                    y = iir_lowpass(vals, ln, k)
                else:  # highp
                    y = vals - iir_lowpass(vals, ln, k)
                return vwrite(st, base, y, ln)
            return op

        B["hull"] = make_filter("hull")
        B["lowp"] = make_filter("lowp")
        B["highp"] = make_filter("highp")

        # -- assemble branch table --------------------------------------------

        num_ops = isa.num_ops
        needs_din = [0] * (num_ops + 1)
        needs_dout = [0] * (num_ops + 1)
        needs_fin = [0] * (num_ops + 1)
        needs_fout = [0] * (num_ops + 1)
        branches: list[Callable] = []
        for code in range(num_ops):
            nm = isa.name[code]
            fn = B.get(nm)
            if fn is None:
                raise RuntimeError(f"opcode {nm!r} not implemented")
            branches.append(fn)
            din, dout, fin, fout = STACK_NEEDS.get(nm, (0, 0, 0, 0))
            needs_din[code], needs_dout[code] = din, dout
            needs_fin[code], needs_fout[code] = fin, fout

        def fios_or_trap(st):
            # opcode >= num_ops: FIOS host call (suspend) or invalid (trap).
            pc = cur_pc(st) - 1
            instr = st.cs[jnp.clip(pc, 0, CS - 1)]
            opcode = (instr >> 2).astype(I32)
            is_fios = opcode >= FIOS_BASE
            def susp(s):
                s = set_pc(s, pc)   # host re-reads the op
                s = s._replace(io_op=s.io_op.at[s.cur].set(opcode))
                return set_status(s, ST_IOWAIT)
            return lax.cond(is_fios, susp, lambda s: raise_exc(s, EXC_TRAP), st)
        branches.append(fios_or_trap)

        NEEDS_DIN = jnp.array(needs_din, I32)
        NEEDS_DOUT = jnp.array(needs_dout, I32)
        NEEDS_FIN = jnp.array(needs_fin, I32)
        NEEDS_FOUT = jnp.array(needs_fout, I32)

        ELIDE = self.elide_checks

        def exec_op(st, opcode):
            code = jnp.clip(opcode, 0, num_ops).astype(I32)
            if ELIDE:
                # Verified program: the stack pre-check is statically dead.
                return lax.switch(code, branches, st)
            t = st.cur
            din = NEEDS_DIN[code]
            dout = NEEDS_DOUT[code]
            fin = NEEDS_FIN[code]
            fout = NEEDS_FOUT[code]
            under = (st.dsp[t] < din) | (st.fsp[t] < fin)
            over = (st.dsp[t] - din + dout > DS) | (st.fsp[t] - fin + fout > FS)
            bad = under | over
            def good(s):
                return lax.switch(code, branches, s)
            return lax.cond(bad, lambda s: raise_exc(s, EXC_STACK), good, st)

        # Exception dispatch (paper §3.8): align RS to the catch point,
        # push the catch point as the return address, enter the handler.
        # Shared by the generic step and the trace-specialized steps.
        def dispatch_exc(s):
            t2 = s.cur
            code = jnp.clip(s.pending_exc[t2], 0, NUM_EXC - 1)
            handler = s.handlers[code]
            has = handler > 0
            def with_handler(x):
                crsp = jnp.clip(x.catch_rsp[t2], 0, RS - 1)
                x = x._replace(
                    rs=x.rs.at[t2, crsp].set(x.catch_pc[t2]),
                    rsp=x.rsp.at[t2].set(crsp + 1),
                    last_exc=x.last_exc.at[t2].set(code),
                    pending_exc=x.pending_exc.at[t2].set(0),
                )
                return set_pc(x, handler)
            def no_handler(x):
                x = x._replace(
                    last_exc=x.last_exc.at[t2].set(code),
                    pending_exc=x.pending_exc.at[t2].set(0),
                )
                return set_status(x, ST_ERR)
            return lax.cond(has, with_handler, no_handler, s)

        def finish_instr(st):
            """Shared per-instruction tail: step count + exception dispatch."""
            st = st._replace(steps=st.steps + 1)
            exc = st.pending_exc[st.cur]
            return lax.cond(exc > 0, dispatch_exc, lambda s: s, st)

        def step_instr(st: VMState) -> VMState:
            t = st.cur
            pc = st.pc[t]
            pc_ok = (pc >= 0) & (pc < CS)
            instr = st.cs[jnp.clip(pc, 0, CS - 1)]
            tag = instr & 3
            payload = (instr >> 2).astype(I32)

            def case_op(s):
                s = set_pc(s, pc + 1)
                return exec_op(s, payload)

            def case_lit(s):
                s = set_pc(s, pc + 1)
                if ELIDE:
                    return dpush(s, payload)
                over = s.dsp[t] >= DS
                return lax.cond(
                    over, lambda x: raise_exc(x, EXC_STACK), lambda x: dpush(x, payload), s
                )

            def case_call(s):
                over = s.rsp[t] >= RS
                def do(x):
                    x = x._replace(
                        rs=x.rs.at[t, jnp.clip(x.rsp[t], 0, RS - 1)].set(pc + 1),
                        rsp=x.rsp.at[t].add(1),
                    )
                    return set_pc(x, payload)
                return lax.cond(over, lambda x: raise_exc(x, EXC_STACK), do, s)

            def case_bad(s):
                return raise_exc(set_pc(s, pc + 1), EXC_TRAP)

            st = lax.cond(
                pc_ok,
                lambda s: lax.switch(tag, [case_op, case_lit, case_call, case_bad], s),
                lambda s: set_status(raise_exc(s, EXC_TRAP), ST_ERR),
                st,
            )
            return finish_instr(st)

        self._step_instr = step_instr

        # -- trace-specialized steps (PyPy-style greens; core/vm/trace.py) ----
        #
        # ``make_static_step(tag, code)`` compiles ONE instruction's
        # semantics with the tag and (for TAG_OP) the dispatch-table branch
        # chosen at build time — the ``lax.switch`` over the whole branch
        # table disappears, only the op body and its data-dependent conds
        # remain.  The instruction *cell* stays a traced operand so literal
        # payloads and call targets do not fragment the trace-fn cache: a
        # whole program family ("lit lit + halt" for any literals) shares
        # one compiled function.  The caller guarantees pc validity by
        # guarding ``pc == recorded_pc`` and ``cs[pc] == recorded_cell``
        # (recorded pcs passed bounds-checked fetch), so the generic step's
        # pc_ok cond is statically true here.  Everything else — stack
        # pre-check, raise/dispatch, step counting — is byte-identical to
        # ``step_instr``.

        def make_static_step(tag: int, code: int):
            tag = int(tag)

            if tag == TAG_OP:
                code = min(max(int(code), 0), num_ops)
                body = branches[code]
                din, dout = needs_din[code], needs_dout[code]
                fin, fout = needs_fin[code], needs_fout[code]

                def step(st, instr):
                    t = st.cur
                    st = set_pc(st, st.pc[t] + 1)
                    if ELIDE:
                        return finish_instr(body(st))
                    under = (st.dsp[t] < din) | (st.fsp[t] < fin)
                    over = (st.dsp[t] - din + dout > DS) | (
                        st.fsp[t] - fin + fout > FS
                    )
                    bad = under | over
                    st = lax.cond(
                        bad, lambda s: raise_exc(s, EXC_STACK), body, st
                    )
                    return finish_instr(st)

            elif tag == TAG_LIT:
                def step(st, instr):
                    t = st.cur
                    payload = (instr >> 2).astype(I32)
                    st = set_pc(st, st.pc[t] + 1)
                    if ELIDE:
                        return finish_instr(dpush(st, payload))
                    over = st.dsp[t] >= DS
                    st = lax.cond(
                        over,
                        lambda x: raise_exc(x, EXC_STACK),
                        lambda x: dpush(x, payload),
                        st,
                    )
                    return finish_instr(st)

            elif tag == TAG_CALL:
                def step(st, instr):
                    t = st.cur
                    pc = st.pc[t]
                    payload = (instr >> 2).astype(I32)
                    over = st.rsp[t] >= RS
                    def do(x):
                        x = x._replace(
                            rs=x.rs.at[t, jnp.clip(x.rsp[t], 0, RS - 1)].set(pc + 1),
                            rsp=x.rsp.at[t].add(1),
                        )
                        return set_pc(x, payload)
                    st = lax.cond(
                        over, lambda x: raise_exc(x, EXC_STACK), do, st
                    )
                    return finish_instr(st)

            else:  # TAG_RESERVED
                def step(st, instr):
                    st = raise_exc(set_pc(st, st.pc[st.cur] + 1), EXC_TRAP)
                    return finish_instr(st)

            return step

        self.make_static_step = make_static_step

        def vmloop(st: VMState, steps: int) -> VMState:
            """Alg. 1: run at most ``steps`` instructions of the current task."""
            def cond(carry):
                s, n = carry
                return (n < steps) & (s.tstatus[s.cur] == ST_RUN)

            def body(carry):
                s, n = carry
                return step_instr(s), n + 1

            st, _ = lax.while_loop(cond, body, (st, jnp.int32(0)))
            return st

        self._vmloop = vmloop

        # scheduler (Alg. 6) ---------------------------------------------------

        T = cfg.max_tasks

        def schedule(st: VMState):
            """Select the next task: IO events > timeouts > ready (Alg. 6)."""
            idx = jnp.arange(T, dtype=I32)
            ev_hit = (st.tstatus == ST_EVENT) & (
                jnp.take(st.mem, jnp.clip(st.ev_addr - MEM_BASE, 0, MEM - 1))
                == st.ev_val
            ) & (st.ev_addr >= MEM_BASE)
            # CS-resident guard variables are also legal:
            ev_hit_cs = (st.tstatus == ST_EVENT) & (st.ev_addr < MEM_BASE) & (
                jnp.take(st.cs, jnp.clip(st.ev_addr, 0, CS - 1)) == st.ev_val
            )
            ev_hit = ev_hit | ev_hit_cs
            to_hit = ((st.tstatus == ST_SLEEP) | (st.tstatus == ST_EVENT)) & (
                st.now >= st.timeout
            )
            ready = st.tstatus == ST_YIELD
            # Class priority: event=3, timeout=2, ready=1; first index wins.
            klass = jnp.where(ev_hit, 3, jnp.where(to_hit, 2, jnp.where(ready, 1, 0)))
            score = klass * T + (T - 1 - idx)
            best = jnp.argmax(score).astype(I32)
            found = klass[best] > 0

            def wake(s):
                k = klass[best]
                was_event = s.tstatus[best] == ST_EVENT
                s = s._replace(cur=best, tstatus=s.tstatus.at[best].set(ST_RUN))
                # await returns status: 0 = event, -1 = timeout (paper Ex. 1).
                def push_status(x, v):
                    return x._replace(
                        ds=x.ds.at[best, jnp.clip(x.dsp[best], 0, DS - 1)].set(v),
                        dsp=x.dsp.at[best].add(1),
                    )
                s = lax.cond(
                    was_event & (k == 3), lambda x: push_status(x, I32(0)), lambda x: x, s
                )
                s = lax.cond(
                    was_event & (k == 2), lambda x: push_status(x, I32(-1)), lambda x: x, s
                )
                return s

            st = lax.cond(found, wake, lambda s: s, st)
            return st, found

        self._schedule = schedule

        def run_slice(st: VMState, steps: int):
            """schedule -> vmloop -> preempt (one Fig. 10 service round)."""
            with jax.named_scope("fleet.schedule"):
                st, found = schedule(st)
            with jax.named_scope("fleet.execute"):
                st = lax.cond(
                    found, lambda s: vmloop(s, steps), lambda s: s, st
                )
                # Preempt a task that exhausted its slice (stays ready).
                still_running = st.tstatus[st.cur] == ST_RUN
                st = lax.cond(
                    still_running,
                    lambda s: s._replace(
                        tstatus=s.tstatus.at[s.cur].set(ST_YIELD)
                    ),
                    lambda s: s,
                    st,
                )
            return st, found

        self._run_slice = run_slice
        # Public functional form: pure (state, steps) -> (state, found), safe
        # to compose under jax.vmap/jit — the seam the fleet/ensemble batched
        # executors are built on.
        self.run_slice_fn = run_slice

        # Executive scheduler ---------------------------------------------------
        # Same runnability classes as `schedule` (Alg. 6), but ties break
        # lexicographically on (class, prio, round-robin rotation from the
        # last-run slot) instead of lowest-index-first.  `rot` is a
        # permutation of 0..T-1 so the argmin among candidates is unique:
        # equal-(class, prio) tasks share the CPU round-robin, which is the
        # starvation-freedom guarantee the Executive tests rely on.

        def schedule_prio(st: VMState):
            idx = jnp.arange(T, dtype=I32)
            ev_hit = (st.tstatus == ST_EVENT) & (
                jnp.take(st.mem, jnp.clip(st.ev_addr - MEM_BASE, 0, MEM - 1))
                == st.ev_val
            ) & (st.ev_addr >= MEM_BASE)
            ev_hit_cs = (st.tstatus == ST_EVENT) & (st.ev_addr < MEM_BASE) & (
                jnp.take(st.cs, jnp.clip(st.ev_addr, 0, CS - 1)) == st.ev_val
            )
            ev_hit = ev_hit | ev_hit_cs
            to_hit = ((st.tstatus == ST_SLEEP) | (st.tstatus == ST_EVENT)) & (
                st.now >= st.timeout
            )
            ready = st.tstatus == ST_YIELD
            klass = jnp.where(ev_hit, 3, jnp.where(to_hit, 2, jnp.where(ready, 1, 0)))
            rot = jnp.mod(idx - st.cur - 1, T)
            neg_inf = jnp.int32(-(2 ** 31))
            kmax = jnp.max(klass)
            cand = klass == kmax
            pmax = jnp.max(jnp.where(cand, st.prio, neg_inf))
            cand = cand & (st.prio == pmax)
            best = jnp.argmin(jnp.where(cand, rot, T)).astype(I32)
            found = kmax > 0

            def wake(s):
                k = klass[best]
                was_event = s.tstatus[best] == ST_EVENT
                s = s._replace(cur=best, tstatus=s.tstatus.at[best].set(ST_RUN))
                def push_status(x, v):
                    return x._replace(
                        ds=x.ds.at[best, jnp.clip(x.dsp[best], 0, DS - 1)].set(v),
                        dsp=x.dsp.at[best].add(1),
                    )
                s = lax.cond(
                    was_event & (k == 3), lambda x: push_status(x, I32(0)), lambda x: x, s
                )
                s = lax.cond(
                    was_event & (k == 2), lambda x: push_status(x, I32(-1)), lambda x: x, s
                )
                return s

            st = lax.cond(found, wake, lambda s: s, st)
            return st, found

        self._schedule_prio = schedule_prio

        def run_slice_exec(st: VMState, steps: int):
            """One Executive micro-slice: schedule_prio -> vmloop -> preempt.

            Returns ``(st, found, switched, preempted)`` so the fleet can
            accumulate task-level counters without a second pass: ``switched``
            is 1 when the dispatcher picked a different slot than last ran,
            ``preempted`` is 1 when the task was still ST_RUN at quantum end.
            """
            prev = st.cur
            with jax.named_scope("fleet.schedule"):
                st, found = schedule_prio(st)
                switched = (found & (st.cur != prev)).astype(I32)
            with jax.named_scope("fleet.execute"):
                st = lax.cond(
                    found, lambda s: vmloop(s, steps), lambda s: s, st
                )
                preempted = st.tstatus[st.cur] == ST_RUN
                st = lax.cond(
                    preempted,
                    lambda s: s._replace(
                        tstatus=s.tstatus.at[s.cur].set(ST_YIELD)
                    ),
                    lambda s: s,
                    st,
                )
            return st, found, switched, preempted.astype(I32)

        self.run_slice_exec_fn = run_slice_exec


@functools.lru_cache(maxsize=8)
def get_interpreter(cfg: VMConfig, elide_checks: bool = False) -> Interpreter:
    """Interpreters are expensive to trace/compile — share per VMConfig
    (the default ISA is a process-wide singleton)."""
    return Interpreter(cfg, elide_checks=elide_checks)


def interp_for(
    cfg: VMConfig, isa: ISA | None = None, elide_checks: bool = False
) -> Interpreter:
    """Shared interpreter-selection policy: the per-config cache for the
    default ISA, a fresh build for a custom one.  Used by every executor
    frontend (JitExecutor, FleetKernels) so they cannot diverge."""
    if isa is None or isa is get_isa():
        return get_interpreter(cfg, elide_checks)
    return Interpreter(cfg, isa, elide_checks=elide_checks)
