"""Worst-case interval analysis: prove every register fits its bitwidth.

An abstract interpreter over jaxprs in the interval domain. Each traced
value is summarized by one closed interval ``[lo, hi]`` covering EVERY
element it can take for ANY program input inside the declared input
intervals (the ADC range ``FixedPointSpec.qmin/qmax`` and the session
register assumptions in ``targets.py``). Bounds are exact Python integers
(arbitrary precision), so the question "does this intermediate fit int32"
is answered by arithmetic, not sampling.

Design choices, in order of load-bearing-ness:

* **Concrete unrolling.** ``scan`` bodies (the 11-iteration MP bisection,
  the blocked FIR solves) unroll up to ``scan_unroll_limit`` iterations,
  and ``pallas_call`` grids unroll per grid step in row-major order with
  CONCRETE ``program_id`` values — so ``pl.when(b == 0)`` init/flush
  predicates resolve exactly and scratch accumulators are bounded by the
  real number of grid steps. Loops beyond the limit fall back to a
  join-until-stable fixpoint with widening to ``[-inf, inf]`` — sound,
  never silently optimistic.
* **Rect-keyed ref cells.** Pallas ``MemRef``s (inputs, outputs, VMEM
  scratch) are mutable cells keyed by the static/resolved index rects of
  their ``get``/``swap`` ops: a full-rect write replaces (strong update),
  an exact-rect write replaces that rect, anything unresolvable joins into
  everything it might touch (weak update). This keeps per-filter partial
  accumulator rows (``part_s[pl.ds(f, 1), :]``) independent instead of
  smearing all filters into one growing hull.
* **Every integer outvar is a register.** Each visited equation records
  the worst-case interval of its integer outputs, the required two's-
  complement bits, and the headroom against the carrier dtype. An interval
  escaping the dtype's representable range is an overflow violation naming
  the equation (primitive, source line, enclosing loop path). The
  per-record table is the static bitwidth column the ROADMAP Pareto
  search consumes.

Float values flow through the same interpreter (so mixed programs don't
crash) but get no bitwidth records: the overflow proof is about the
integer carrier.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import re
from typing import NamedTuple, Optional

import numpy as np

from repro.analysis import traverse

INF = float("inf")


def _isinf(v) -> bool:
    return isinstance(v, float) and math.isinf(v)


class Interval(NamedTuple):
    """Closed interval; bounds are exact ints for integer values (or
    +-inf), floats for float values."""
    lo: object
    hi: object

    def join(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    @property
    def concrete(self) -> bool:
        return self.lo == self.hi and not isinstance(self.lo, float)

    def __repr__(self) -> str:  # compact report form
        return f"[{self.lo}, {self.hi}]"


TOP = Interval(-INF, INF)
BOOL = Interval(0, 1)


def signed_bits(iv: Interval) -> object:
    """Smallest two's-complement width holding every value in ``iv``:
    ``n`` with ``-2**(n-1) <= lo`` and ``hi <= 2**(n-1) - 1``. Infinite
    bounds need infinite bits."""
    if _isinf(iv.lo) or _isinf(iv.hi):
        return INF
    lo, hi = int(iv.lo), int(iv.hi)
    n_hi = hi.bit_length() + 1 if hi >= 0 else 1
    n_lo = (-lo - 1).bit_length() + 1 if lo < 0 else 1
    return max(n_lo, n_hi, 1)


def carrier_bits(iv: Interval, *, unsigned: bool = False) -> object:
    """Smallest register width of the carrier's signedness family holding
    every value in ``iv``: two's-complement for signed carriers, plain
    binary for unsigned ones (a negative bound fits no unsigned width)."""
    if _isinf(iv.lo) or _isinf(iv.hi):
        return INF
    if unsigned:
        if iv.lo < 0:
            return INF
        return max(int(iv.hi).bit_length(), 1)
    return signed_bits(iv)


def _json_bound(v):
    return None if _isinf(v) else int(v)


def _dtype_bits(dtype) -> Optional[int]:
    """Carrier width for integer dtypes; None for float/bool (no overflow
    semantics to check)."""
    d = np.dtype(dtype)
    if d.kind in ("i", "u"):
        return d.itemsize * 8
    return None


def _dtype_range(dtype) -> Interval:
    d = np.dtype(dtype)
    if d.kind == "b":
        return BOOL
    if d.kind in ("i", "u"):
        info = np.iinfo(d)
        return Interval(int(info.min), int(info.max))
    return TOP


def _from_value(val) -> Interval:
    """Interval of a concrete constant (literal or jaxpr const)."""
    arr = np.asarray(val)
    if arr.size == 0:
        return Interval(0, 0)
    if arr.dtype.kind in ("i", "u", "b"):
        return Interval(int(arr.min()), int(arr.max()))
    lo, hi = float(arr.min()), float(arr.max())
    if math.isnan(lo) or math.isnan(hi):
        return TOP
    return Interval(lo, hi)


# ---------------------------------------------------------------------------
# mutable cells for pallas MemRefs
# ---------------------------------------------------------------------------


_SLICE_RE = re.compile(r"Slice\[\((\d+|None), (\d+), (\d+)\)\]")


def _parse_indexer(tree_param, ndim: int):
    """Decode the static part of a ``get``/``swap`` NDIndexer PyTreeDef:
    a list of ``(start|None, size)`` per dim (None = dynamic start, which
    consumes one index invar), or None when the structure isn't the plain
    all-slices form (integer indexing, multiple indexers, strides != 1)."""
    dims = _SLICE_RE.findall(str(tree_param))
    if len(dims) != ndim:
        return None
    out = []
    for start, size, stride in dims:
        if stride != "1":
            return None
        out.append((None if start == "None" else int(start), int(size)))
    return out


def _rects_overlap(a, b) -> bool:
    return all(s1 < e2 and s2 < e1 for (s1, e1), (s2, e2) in zip(a, b))


def _rect_contains(outer, inner) -> bool:
    return all(s1 <= s2 and e2 <= e1
               for (s1, e1), (s2, e2) in zip(outer, inner))


class RefCell:
    """Interval state of one MemRef: a background hull plus strong-updated
    rects. ``background=None`` means never-written: a read that no
    recorded write covers is a read-before-write (real UB in a pallas
    kernel) and is reported by the interpreter."""

    def __init__(self, shape, dtype, background: Optional[Interval]):
        self.shape = tuple(int(d) for d in shape)
        self.dtype = dtype
        self.background = background
        self.rects: dict = {}

    def _full_rect(self):
        return tuple((0, d) for d in self.shape)

    def resolve_rect(self, tree_param, idx_vals):
        """Static+concrete index rect of an access, or None (unresolvable
        -> weak semantics). ``idx_vals`` are the evaluated intervals of the
        dynamic index operands, consumed in order."""
        dims = _parse_indexer(tree_param, len(self.shape))
        if dims is None:
            return None
        rect, k = [], 0
        for (start, size) in dims:
            if start is None:
                if k >= len(idx_vals):
                    return None
                iv = idx_vals[k]
                k += 1
                if not iv.concrete:
                    return None
                start = int(iv.lo)
            rect.append((start, start + size))
        if k != len(idx_vals):
            return None
        return tuple(rect)

    def read(self, rect) -> Optional[Interval]:
        """Join of everything the accessed rect can contain. ``None``
        means the rect is provably unwritten (read-before-write)."""
        if rect is None:
            rect = self._full_rect()
        out = None
        for r, iv in self.rects.items():
            if _rects_overlap(r, rect):
                out = iv if out is None else out.join(iv)
        covered = any(_rect_contains(r, rect) for r in self.rects)
        if not covered and self.background is not None:
            out = (self.background if out is None
                   else out.join(self.background))
        return out

    def write(self, rect, value: Interval) -> None:
        if rect is None:
            # unresolvable target: the write may land anywhere (weak)
            self.background = (value if self.background is None
                               else self.background.join(value))
            for r in self.rects:
                self.rects[r] = self.rects[r].join(value)
            return
        if rect == self._full_rect():
            self.background = value
            self.rects = {}
            return
        self.rects[rect] = value

    def hull(self) -> Interval:
        out = self.background
        for iv in self.rects.values():
            out = iv if out is None else out.join(iv)
        return out if out is not None else Interval(0, 0)

    def snapshot(self):
        return (self.background, dict(self.rects))

    def restore(self, snap) -> None:
        self.background, rects = snap
        self.rects = dict(rects)

    def join_state(self, snap) -> None:
        bg, rects = snap
        if self.background is None:
            self.background = bg
        elif bg is not None:
            self.background = self.background.join(bg)
        for r, iv in rects.items():
            self.rects[r] = iv if r not in self.rects \
                else self.rects[r].join(iv)


# ---------------------------------------------------------------------------
# records + results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RegisterRecord:
    """Worst-case summary of one traced equation's integer output."""
    name: str          # path/primitive@source
    primitive: str
    path: str
    source: str
    dtype_bits: int
    lo: object
    hi: object
    visits: int = 1
    unsigned: bool = False

    @property
    def required_bits(self) -> object:
        return carrier_bits(Interval(self.lo, self.hi),
                            unsigned=self.unsigned)

    @property
    def headroom_bits(self) -> object:
        r = self.required_bits
        return -INF if r == INF else self.dtype_bits - r

    def to_dict(self) -> dict:
        rb = self.required_bits
        return {
            "name": self.name,
            "dtype_bits": self.dtype_bits,
            "interval": [_json_bound(self.lo), _json_bound(self.hi)],
            "required_bits": _json_bound(rb),
            "headroom_bits": (None if rb == INF
                              else int(self.dtype_bits - rb)),
            "visits": self.visits,
        }


@dataclasses.dataclass(frozen=True)
class OverflowViolation:
    """One integer intermediate whose worst case exceeds its carrier."""
    name: str
    primitive: str
    source: str
    dtype_bits: int
    required_bits: object
    lo: object
    hi: object

    def to_dict(self) -> dict:
        return {
            "name": self.name, "primitive": self.primitive,
            "source": self.source, "dtype_bits": self.dtype_bits,
            "required_bits": _json_bound(self.required_bits),
            "interval": [_json_bound(self.lo), _json_bound(self.hi)],
        }


@dataclasses.dataclass
class IntervalResult:
    """Everything the pass proved about one target program."""
    ok: bool
    violations: list
    registers: list                  # RegisterRecord, sorted by headroom
    out_intervals: list              # Interval per program output
    min_headroom_bits: object
    max_required_bits: object
    unsupported: list                # primitives handled conservatively
    # per-equation records keyed ``(path, id(eqn))`` — the lookup the IR
    # builder (repro.ir.build) uses to type registers; only valid while
    # the analyzed jaxpr objects are alive (same-process consumption)
    records_by_eqn: dict = dataclasses.field(default_factory=dict)

    def to_dict(self, *, top_registers: int = 20) -> dict:
        return {
            "ok": self.ok,
            "min_headroom_bits": _json_bound(self.min_headroom_bits),
            "max_required_bits": _json_bound(self.max_required_bits),
            "num_registers": len(self.registers),
            "violations": [v.to_dict() for v in self.violations],
            "tightest_registers": [r.to_dict()
                                   for r in self.registers[:top_registers]],
            "out_intervals": [[_json_bound(iv.lo), _json_bound(iv.hi)]
                              for iv in self.out_intervals],
            "unsupported_primitives": sorted(set(self.unsupported)),
        }


# ---------------------------------------------------------------------------
# transfer functions
# ---------------------------------------------------------------------------


def _mul_iv(a: Interval, b: Interval) -> Interval:
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            if (_isinf(x) and y == 0) or (_isinf(y) and x == 0):
                cands.append(0)
            else:
                cands.append(x * y)
    return Interval(min(cands), max(cands))


def _shift_right_iv(a: Interval, k: Interval) -> Interval:
    if _isinf(a.lo) or _isinf(a.hi):
        return TOP
    klo = 0 if _isinf(k.lo) else max(int(k.lo), 0)
    khi = 63 if _isinf(k.hi) else max(int(k.hi), 0)
    cands = [int(x) >> kk for x in (a.lo, a.hi) for kk in (klo, khi)]
    return Interval(min(cands), max(cands))


def _shift_left_iv(a: Interval, k: Interval) -> Interval:
    if _isinf(a.lo) or _isinf(a.hi) or _isinf(k.hi):
        return TOP
    klo = 0 if _isinf(k.lo) else max(int(k.lo), 0)
    khi = max(int(k.hi), 0)
    cands = [int(x) << kk for x in (a.lo, a.hi) for kk in (klo, khi)]
    return Interval(min(cands), max(cands))


def _bitwise_iv(a: Interval, b: Interval) -> Interval:
    """AND/OR/XOR stay within the wider operand's two's-complement width."""
    if a.lo >= 0 and b.lo >= 0 and not (_isinf(a.hi) or _isinf(b.hi)):
        # n-bit nonneg operands produce an n-bit nonneg result
        n = max(int(a.hi), int(b.hi)).bit_length()
        return Interval(0, (1 << n) - 1 if n else 0)
    na, nb = signed_bits(a), signed_bits(b)
    if na == INF or nb == INF:
        return TOP
    n = max(na, nb)
    return Interval(-(1 << (n - 1)), (1 << (n - 1)) - 1)


def _cmp(op, a: Interval, b: Interval) -> Interval:
    """Comparison to a bool interval, resolved when operands are disjoint."""
    if op == "lt":
        if a.hi < b.lo:
            return Interval(1, 1)
        if a.lo >= b.hi:
            return Interval(0, 0)
    elif op == "le":
        if a.hi <= b.lo:
            return Interval(1, 1)
        if a.lo > b.hi:
            return Interval(0, 0)
    elif op == "gt":
        if a.lo > b.hi:
            return Interval(1, 1)
        if a.hi <= b.lo:
            return Interval(0, 0)
    elif op == "ge":
        if a.lo >= b.hi:
            return Interval(1, 1)
        if a.hi < b.lo:
            return Interval(0, 0)
    elif op == "eq":
        if a.concrete and b.concrete and a.lo == b.lo:
            return Interval(1, 1)
        if a.hi < b.lo or b.hi < a.lo:
            return Interval(0, 0)
    elif op == "ne":
        if a.concrete and b.concrete and a.lo == b.lo:
            return Interval(0, 0)
        if a.hi < b.lo or b.hi < a.lo:
            return Interval(1, 1)
    return BOOL


def _reduced_elems(eqn) -> int:
    shape = getattr(eqn.invars[0].aval, "shape", ())
    m = 1
    for a in eqn.params.get("axes", ()):
        m *= shape[a]
    return m


def _sum_iv(x: Interval, m: int) -> Interval:
    """Sum of ``m`` elements each in ``x``."""
    if m <= 0:
        return Interval(0, 0)
    return Interval(x.lo * m, x.hi * m)


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class _Analyzer:
    def __init__(self, *, scan_unroll_limit: int = 64,
                 grid_unroll_limit: int = 4096,
                 fixpoint_iters: int = 64):
        self.scan_unroll_limit = scan_unroll_limit
        self.grid_unroll_limit = grid_unroll_limit
        self.fixpoint_iters = fixpoint_iters
        self.records: dict = {}
        self.violations: list = []
        self.unsupported: list = []
        self._pid_stack: list = []   # concrete program_id per grid axis
        self._grid_stack: list = []  # static grid tuple

    # -- environment ------------------------------------------------------

    def _read(self, env, v):
        from jax._src.core import Literal
        if isinstance(v, Literal):
            return _from_value(v.val)
        return env[v]

    def _name(self, eqn, path) -> str:
        from repro.analysis.traverse import eqn_source
        return f"{path}/{eqn.primitive.name}@{eqn_source(eqn)}"

    def _check_and_record(self, eqn, path, iv: Interval, outvar) -> None:
        dtype = getattr(outvar.aval, "dtype", None)
        if dtype is None:
            return
        bits = _dtype_bits(dtype)
        if bits is None:
            return
        unsigned = np.dtype(dtype).kind == "u"
        from repro.analysis.traverse import eqn_source
        key = (path, id(eqn))
        rec = self.records.get(key)
        if rec is None:
            self.records[key] = RegisterRecord(
                name=self._name(eqn, path),
                primitive=eqn.primitive.name, path=path,
                source=eqn_source(eqn), dtype_bits=bits,
                lo=iv.lo, hi=iv.hi, unsigned=unsigned)
        else:
            rec.lo = min(rec.lo, iv.lo)
            rec.hi = max(rec.hi, iv.hi)
            rec.visits += 1
        rng = _dtype_range(dtype)
        if iv.lo < rng.lo or iv.hi > rng.hi:
            self.violations.append(OverflowViolation(
                name=self._name(eqn, path),
                primitive=eqn.primitive.name, source=eqn_source(eqn),
                dtype_bits=bits,
                required_bits=carrier_bits(iv, unsigned=unsigned),
                lo=iv.lo, hi=iv.hi))

    def _bind_outs(self, eqn, env, path, outs) -> None:
        # NB: Interval is itself a tuple — test it before the sequence case
        if isinstance(outs, Interval) or not isinstance(outs, (list, tuple)):
            outs = [outs]
        for v, iv in zip(eqn.outvars, outs):
            env[v] = iv
            if isinstance(iv, Interval):
                self._check_and_record(eqn, path, iv, v)

    # -- jaxpr evaluation --------------------------------------------------

    def eval_closed(self, closed, in_vals, path=""):
        consts = [c if isinstance(c, (Interval, RefCell))
                  else _from_value(c) for c in closed.consts]
        return self.eval_jaxpr(closed.jaxpr, consts + list(in_vals), path)

    def eval_jaxpr(self, jaxpr, in_vals, path=""):
        env = {}
        allvars = list(jaxpr.constvars) + list(jaxpr.invars)
        if len(allvars) != len(in_vals):
            raise ValueError(
                f"arity mismatch at {path or '<top>'}: {len(allvars)} "
                f"vars, {len(in_vals)} values")
        for v, val in zip(allvars, in_vals):
            env[v] = val
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in traverse.CALL_PRIMS or name == traverse.VJP_JAXPR_PRIM:
                self._eval_call(eqn, env, path)
            elif name == "scan":
                self._eval_scan(eqn, env, path)
            elif name == "while":
                self._eval_while(eqn, env, path)
            elif name == "cond":
                self._eval_cond(eqn, env, path)
            elif name == "pallas_call":
                self._eval_pallas(eqn, env, path)
            elif name == "get":
                self._bind_outs(eqn, env, path,
                                self._eval_get(eqn, env, path))
            elif name == "swap":
                self._bind_outs(eqn, env, path,
                                self._eval_swap(eqn, env, path))
            else:
                self._bind_outs(eqn, env, path,
                                self._eval_leaf(eqn, env, path))
        return [self._read(env, v) for v in jaxpr.outvars]

    # -- leaf ops ----------------------------------------------------------

    IDENTITY = {
        "broadcast_in_dim", "reshape", "squeeze", "expand_dims",
        "transpose", "rev", "slice", "gather", "copy", "device_put",
        "stop_gradient", "reduce_max", "reduce_min", "cummax", "cummin",
        "reduce_precision", "dynamic_slice",
    }

    def _eval_leaf(self, eqn, env, path):
        name = eqn.primitive.name
        ins = [self._read(env, v) for v in eqn.invars]

        if name in self.IDENTITY:
            return [ins[0]] * len(eqn.outvars)
        if name == "dynamic_update_slice":
            return ins[0].join(ins[1])
        if name == "concatenate":
            out = ins[0]
            for iv in ins[1:]:
                out = out.join(iv)
            return out
        if name == "pad":
            return ins[0].join(ins[1])
        if name == "add":
            return Interval(ins[0].lo + ins[1].lo, ins[0].hi + ins[1].hi)
        if name == "sub":
            return Interval(ins[0].lo - ins[1].hi, ins[0].hi - ins[1].lo)
        if name == "neg":
            return Interval(-ins[0].hi, -ins[0].lo)
        if name == "mul":
            return _mul_iv(ins[0], ins[1])
        if name == "max":
            return Interval(max(ins[0].lo, ins[1].lo),
                            max(ins[0].hi, ins[1].hi))
        if name == "min":
            return Interval(min(ins[0].lo, ins[1].lo),
                            min(ins[0].hi, ins[1].hi))
        if name == "abs":
            lo, hi = ins[0]
            return Interval(0 if lo <= 0 <= hi else min(abs(lo), abs(hi)),
                            max(abs(lo), abs(hi)))
        if name == "sign":
            lo, hi = ins[0]
            return Interval(-1 if lo < 0 else (1 if lo > 0 else 0),
                            1 if hi > 0 else (-1 if hi < 0 else 0))
        if name == "clamp":
            lo_b, x, hi_b = ins
            t = Interval(max(x.lo, lo_b.lo), max(x.hi, lo_b.hi))
            return Interval(min(t.lo, hi_b.lo), min(t.hi, hi_b.hi))
        if name in ("gt", "lt", "ge", "le", "eq", "ne"):
            return _cmp(name, ins[0], ins[1])
        if name == "select_n":
            pred, cases = ins[0], ins[1:]
            if pred.concrete and 0 <= int(pred.lo) < len(cases):
                return cases[int(pred.lo)]
            lo = 0 if _isinf(pred.lo) else max(int(pred.lo), 0)
            hi = len(cases) - 1 if _isinf(pred.hi) \
                else min(int(pred.hi), len(cases) - 1)
            out = cases[lo]
            for c in cases[lo + 1:hi + 1]:
                out = out.join(c)
            return out
        if name == "shift_left":
            return _shift_left_iv(ins[0], ins[1])
        if name == "shift_right_arithmetic":
            return _shift_right_iv(ins[0], ins[1])
        if name == "shift_right_logical":
            if ins[0].lo >= 0:
                return _shift_right_iv(ins[0], ins[1])
            return _dtype_range(eqn.outvars[0].aval.dtype)
        if name in ("and", "or", "xor"):
            if np.dtype(eqn.outvars[0].aval.dtype).kind == "b":
                return BOOL
            if name == "and" and ins[0].lo >= 0 and ins[1].lo >= 0:
                # nonneg AND clears bits: x & y <= min(x, y)
                return Interval(0, min(ins[0].hi, ins[1].hi))
            return _bitwise_iv(ins[0], ins[1])
        if name == "not":
            if np.dtype(eqn.outvars[0].aval.dtype).kind == "b":
                return BOOL
            return Interval(-ins[0].hi - 1, -ins[0].lo - 1)
        if name == "reduce_sum":
            return _sum_iv(ins[0], _reduced_elems(eqn))
        if name == "cumsum":
            shape = getattr(eqn.invars[0].aval, "shape", ())
            m = shape[eqn.params.get("axis", 0)] if shape else 1
            # prefix sums: hull over k in 1..m partial sums (linear in k)
            s1, sm = _sum_iv(ins[0], 1), _sum_iv(ins[0], m)
            return s1.join(sm)
        if name in ("reduce_and", "reduce_or"):
            return BOOL
        if name in ("argmax", "argmin"):
            return Interval(0, max(_reduced_elems(eqn) - 1, 0))
        if name == "iota":
            shape = eqn.params.get("shape", ())
            dim = eqn.params.get("dimension", 0)
            n = shape[dim] if shape else 1
            return Interval(0, max(int(n) - 1, 0))
        if name == "convert_element_type":
            return self._convert(eqn, ins[0])
        if name == "program_id":
            axis = eqn.params.get("axis", 0)
            if self._pid_stack and self._pid_stack[-1] is not None:
                v = self._pid_stack[-1][axis]
                return Interval(v, v)
            if self._grid_stack:
                return Interval(0, max(self._grid_stack[-1][axis] - 1, 0))
            return Interval(0, 0)
        if name == "num_programs":
            axis = eqn.params.get("axis", 0)
            g = self._grid_stack[-1][axis] if self._grid_stack else 1
            return Interval(g, g)
        if name == "dot_general":
            lhs_shape = eqn.invars[0].aval.shape
            ((lc, _), _) = eqn.params["dimension_numbers"]
            m = 1
            for d in lc:
                m *= lhs_shape[d]
            return _sum_iv(_mul_iv(ins[0], ins[1]), m)
        if name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape
            k_elems = 1
            for d in rhs:
                k_elems *= d
            m = max(k_elems // max(rhs[0], 1), 1)
            return _sum_iv(_mul_iv(ins[0], ins[1]), m)
        if name == "integer_pow":
            y = eqn.params.get("y", 1)
            if _isinf(ins[0].lo) or _isinf(ins[0].hi):
                return TOP
            cands = [x ** y for x in (ins[0].lo, ins[0].hi)]
            if y % 2 == 0 and ins[0].lo <= 0 <= ins[0].hi:
                cands.append(0)
            return Interval(min(cands), max(cands))
        if name == "rem":
            a, b = ins
            if _isinf(b.lo) or _isinf(b.hi) or (b.lo <= 0 <= b.hi):
                return TOP
            m = max(abs(int(b.lo)), abs(int(b.hi))) - 1
            return Interval(-m if a.lo < 0 else 0, m if a.hi > 0 else 0)
        if name == "exp":
            lo = 0.0 if _isinf(ins[0].lo) else math.exp(min(ins[0].lo, 700))
            hi = INF if _isinf(ins[0].hi) else math.exp(min(ins[0].hi, 700))
            return Interval(lo, hi)
        if name == "tanh":
            return Interval(-1.0, 1.0)
        if name == "logistic":
            return Interval(0.0, 1.0)
        if name in ("sqrt", "rsqrt", "log", "div", "pow", "erf", "sin",
                    "cos", "floor", "ceil", "round", "nextafter",
                    "square", "is_finite", "sort"):
            # float-path ops: no integer overflow semantics to prove
            self.unsupported.append(name)
            return [TOP] * len(eqn.outvars)

        self.unsupported.append(name)
        return [_dtype_range(getattr(v.aval, "dtype", np.float32))
                for v in eqn.outvars]

    def _convert(self, eqn, x: Interval) -> Interval:
        dtype = eqn.params.get("new_dtype", eqn.outvars[0].aval.dtype)
        if _dtype_bits(dtype) is None:
            return x
        if isinstance(x.lo, float) or isinstance(x.hi, float):
            if _isinf(x.lo) or _isinf(x.hi):
                return _dtype_range(dtype)
            x = Interval(int(math.floor(x.lo)), int(math.ceil(x.hi)))
        # int narrowing wraps in XLA: a wrap IS an overflow event, which
        # _check_and_record reports (the pre-clamp interval escapes the
        # target range); continue with the full target range so downstream
        # stays sound
        rng = _dtype_range(dtype)
        if x.lo < rng.lo or x.hi > rng.hi:
            return x  # reported at the record step; caller sees true hull
        return x

    # -- higher-order ops --------------------------------------------------

    def _eval_call(self, eqn, env, path):
        closed = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                  or eqn.params.get("fun_jaxpr"))
        ins = [self._read(env, v) for v in eqn.invars]
        sub = f"{path}/{eqn.primitive.name}"
        if hasattr(closed, "consts"):
            outs = self.eval_closed(closed, ins, sub)
        else:
            outs = self.eval_jaxpr(closed, ins, sub)
        self._bind_outs(eqn, env, path, outs)

    def _eval_scan(self, eqn, env, path):
        p = eqn.params
        closed = p["jaxpr"]
        # length 0 is a real case (zero-length chunk programs): the body
        # never runs, the carry out IS the carry in, and the stacked ys are
        # empty arrays (bound to [0, 0] below via the ys-None fallback)
        length = p.get("length")
        length = 1 if length is None else int(length)
        n_consts, n_carry = p["num_consts"], p["num_carry"]
        ins = [self._read(env, v) for v in eqn.invars]
        consts = ins[:n_consts]
        carry = list(ins[n_consts:n_consts + n_carry])
        xs = ins[n_consts + n_carry:]
        n_ys = len(eqn.outvars) - n_carry
        ys = [None] * n_ys
        spath = f"{path}/scan[{length}]"

        def step(cur):
            outs = self.eval_closed(closed, consts + cur + xs, spath)
            return outs[:n_carry], outs[n_carry:]

        def join_ys(acc, new):
            return [b if a is None else a.join(b) for a, b in zip(acc, new)]

        if length <= self.scan_unroll_limit:
            for _ in range(length):
                carry, y = step(carry)
                ys = join_ys(ys, y)
        else:
            stable = False
            for _ in range(self.fixpoint_iters):
                new_carry, y = step(carry)
                ys = join_ys(ys, y)
                joined = [a.join(b) for a, b in zip(carry, new_carry)]
                if all(a.lo == j.lo and a.hi == j.hi
                       for a, j in zip(carry, joined)):
                    stable = True
                    break
                carry = joined
            if not stable:
                carry = [TOP] * len(carry)
                carry, y = step(carry)
                ys = join_ys(ys, y)
        outs = carry + [y if y is not None else Interval(0, 0) for y in ys]
        self._bind_outs(eqn, env, path, outs)

    def _eval_while(self, eqn, env, path):
        p = eqn.params
        cond_n, body_n = p["cond_nconsts"], p["body_nconsts"]
        body = p["body_jaxpr"]
        ins = [self._read(env, v) for v in eqn.invars]
        body_consts = ins[cond_n:cond_n + body_n]
        carry = list(ins[cond_n + body_n:])
        wpath = f"{path}/while"
        stable = False
        for _ in range(self.fixpoint_iters):
            outs = self.eval_closed(body, body_consts + carry, wpath)
            joined = [a.join(b) for a, b in zip(carry, outs)]
            if all(a.lo == j.lo and a.hi == j.hi
                   for a, j in zip(carry, joined)):
                stable = True
                break
            carry = joined
        if not stable:
            carry = [TOP] * len(carry)
            self.eval_closed(body, body_consts + carry, wpath)
        self._bind_outs(eqn, env, path, carry)

    def _eval_cond(self, eqn, env, path):
        branches = eqn.params["branches"]
        ins = [self._read(env, v) for v in eqn.invars]
        index, ops = ins[0], ins[1:]
        if index.concrete:
            lo = hi = max(0, min(int(index.lo), len(branches) - 1))
        else:
            lo = 0 if _isinf(index.lo) else max(int(index.lo), 0)
            hi = len(branches) - 1 if _isinf(index.hi) \
                else min(int(index.hi), len(branches) - 1)
        cells = [o for o in ops if isinstance(o, RefCell)]
        snaps = [c.snapshot() for c in cells]
        end_states: list = []
        outs_join = None
        for b in range(lo, hi + 1):
            for c, s in zip(cells, snaps):
                c.restore(s)
            outs = self.eval_closed(branches[b], ops,
                                    f"{path}/cond.branch{b}")
            end_states.append([c.snapshot() for c in cells])
            if outs_join is None:
                outs_join = list(outs)
            else:
                outs_join = [a.join(o) if isinstance(a, Interval) else a
                             for a, o in zip(outs_join, outs)]
        for i, c in enumerate(cells):
            c.restore(end_states[0][i])
            for st in end_states[1:]:
                c.join_state(st[i])
        self._bind_outs(eqn, env, path, outs_join or [])

    def _eval_pallas(self, eqn, env, path):
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in (getattr(gm, "grid", ()) or ()))
        inner = eqn.params["jaxpr"]
        ins = [self._read(env, v) for v in eqn.invars]
        n_index = int(getattr(gm, "num_index_operands", 0) or 0)
        n_outputs = int(getattr(gm, "num_outputs", len(eqn.outvars))
                        or len(eqn.outvars))
        n_inputs_attr = getattr(gm, "num_inputs", None)
        n_inputs = (int(n_inputs_attr) if n_inputs_attr is not None
                    else len(ins) - n_index)
        # kernel invars: [index scalars, input refs, output refs, scratch]
        cells = []
        for i, kv in enumerate(inner.invars):
            aval = kv.aval
            shape = tuple(getattr(aval, "shape", ()))
            dtype = getattr(aval, "dtype", np.int32)
            if i < n_index:
                cells.append(ins[i])           # scalar prefetch: a value
            elif i < n_index + n_inputs:
                cells.append(RefCell(shape, dtype, ins[i]))
            else:
                cells.append(RefCell(shape, dtype, None))
        steps = 1
        for g in grid:
            steps *= g
        ppath = f"{path}/pallas_call"
        self._grid_stack.append(grid or (1,))
        if 0 < steps <= self.grid_unroll_limit:
            for pid in (itertools.product(*[range(g) for g in grid])
                        if grid else [()]):
                self._pid_stack.append(tuple(pid) if pid else (0,))
                self.eval_jaxpr(inner, cells, ppath)
                self._pid_stack.pop()
        else:
            self._pid_stack.append(None)
            stable = False
            for _ in range(self.fixpoint_iters):
                before = [c.hull() if isinstance(c, RefCell) else c
                          for c in cells]
                self.eval_jaxpr(inner, cells, ppath)
                after = [c.hull() if isinstance(c, RefCell) else c
                         for c in cells]
                if all((not isinstance(b, Interval))
                       or (b.lo == a.lo and b.hi == a.hi)
                       for b, a in zip(before, after)):
                    stable = True
                    break
            if not stable:
                # still-growing ref state after fixpoint_iters: widen every
                # cell to TOP (mirroring _eval_scan's carry fallback — ref
                # writes are strong updates, so no per-cell stability
                # argument survives non-convergence) and run the body once
                # more so reads of the widened state are recorded as
                # violations instead of the loop exiting optimistically
                for c in cells:
                    if isinstance(c, RefCell):
                        c.background = TOP
                        c.rects = {}
                self.eval_jaxpr(inner, cells, ppath)
            self._pid_stack.pop()
        self._grid_stack.pop()
        out_cells = cells[n_index + n_inputs:n_index + n_inputs + n_outputs]
        outs = [c.hull() if isinstance(c, RefCell) else c
                for c in out_cells]
        self._bind_outs(eqn, env, path, outs)

    def _eval_get(self, eqn, env, path):
        ref = env[eqn.invars[0]]
        idx = [self._read(env, v) for v in eqn.invars[1:]]
        rect = ref.resolve_rect(eqn.params.get("tree"), idx)
        out = ref.read(rect)
        if out is None:
            self.violations.append(OverflowViolation(
                name=f"{self._name(eqn, path)} (read-before-write)",
                primitive="get",
                source=self._name(eqn, path).rsplit("@", 1)[-1],
                dtype_bits=_dtype_bits(ref.dtype) or 0,
                required_bits=INF, lo=-INF, hi=INF))
            out = _dtype_range(ref.dtype)
        return out

    def _eval_swap(self, eqn, env, path):
        from jax._src.core import DropVar
        ref = env[eqn.invars[0]]
        val = self._read(env, eqn.invars[1])
        idx = [self._read(env, v) for v in eqn.invars[2:]]
        rect = ref.resolve_rect(eqn.params.get("tree"), idx)
        old = ref.read(rect)
        ref.write(rect, val)
        if old is None:
            # plain stores lower to swap with a DropVar result: writing an
            # unwritten ref is fine, it's only a read-before-write when the
            # old value is actually consumed
            if all(isinstance(v, DropVar) for v in eqn.outvars):
                return val
            self.violations.append(OverflowViolation(
                name=f"{self._name(eqn, path)} (read-before-write)",
                primitive="swap",
                source=self._name(eqn, path).rsplit("@", 1)[-1],
                dtype_bits=_dtype_bits(ref.dtype) or 0,
                required_bits=INF, lo=-INF, hi=INF))
            old = _dtype_range(ref.dtype)
        return old


def analyze_intervals(closed_jaxpr, in_intervals, *,
                      scan_unroll_limit: int = 64,
                      grid_unroll_limit: int = 4096) -> IntervalResult:
    """Run worst-case interval analysis over a ``ClosedJaxpr``.

    ``in_intervals`` is one :class:`Interval` per flattened program input
    (same order as ``jaxpr.invars`` — i.e. ``jax.tree_util.tree_leaves``
    order of the traced arguments). Returns an :class:`IntervalResult`
    whose ``ok`` proves every integer intermediate fits its carrier dtype
    for every input in the declared intervals.
    """
    a = _Analyzer(scan_unroll_limit=scan_unroll_limit,
                  grid_unroll_limit=grid_unroll_limit)
    outs = a.eval_closed(closed_jaxpr, list(in_intervals))
    regs = sorted(a.records.values(),
                  key=lambda r: (r.headroom_bits
                                 if not _isinf(r.headroom_bits)
                                 else -10**9))
    heads = [r.headroom_bits for r in regs]
    reqs = [r.required_bits for r in regs]
    return IntervalResult(
        ok=not a.violations, violations=a.violations, registers=regs,
        out_intervals=[o if isinstance(o, Interval) else TOP
                       for o in outs],
        min_headroom_bits=min(heads) if heads else INF,
        max_required_bits=max(reqs) if reqs else 0,
        unsupported=a.unsupported,
        records_by_eqn=dict(a.records))
