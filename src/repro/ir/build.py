"""Lower a traced integer jaxpr into the typed op-stream IR.

The lowering is deliberately 1:1 with the traced program: every leaf jaxpr
equation becomes exactly one IR instruction (``jit``/call wrappers are
inlined with no instruction, ``scan`` becomes one ``loop`` with a body
region, ``pallas_call`` one ``grid`` region), so the IR census
(``repro.ir.census``) reproduces the jaxpr-walk census numbers EXACTLY —
there is no re-association, fusion or strength reduction that could move
the committed ``hw.*`` benchmark rows. The single rewrite the builder does
perform is the one hardware demands anyway: a ``mul`` whose multiplier is
a positive pow2 literal (the only multiplies the legality whitelist
admits) is folded into a ``shl`` immediate — which is also how the census
already classifies it, so even that moves no numbers.

Register typing: pass ``in_intervals`` (one
:class:`repro.analysis.intervals.Interval` per flattened program input)
and the builder runs the worst-case interval pass over the SAME
``ClosedJaxpr`` object, then keys each equation's proven interval /
minimal bitwidth by ``(path, id(eqn))`` — the builder's recursion
replicates the analyzer's path strings exactly (``""`` at top,
``/jit`` for inlined calls, ``/scan[N]`` for loop bodies,
``/pallas_call`` for grid kernels), so every IR register carries the fact
the static proof established for its defining equation.

Anything outside the multiplierless integer contract — a float dtype, a
real multiply, a divide, ``cond``/``while``/``scatter`` — fails the build
loudly with the offending equation's source location. "Expressible in the
IR" IS the legality proof, by construction.
"""

from __future__ import annotations

import math

import numpy as np

from repro.analysis.traverse import CALL_PRIMS, VJP_JAXPR_PRIM
from repro.ir.isa import Instr, Program, Reg, Region, Rom

# leaf jax primitives with a direct IR opcode (same-arity, srcs = invars)
_DIRECT = {
    "add": "add", "sub": "sub", "neg": "neg", "max": "max", "min": "min",
    "abs": "abs", "sign": "sign", "clamp": "clamp",
    "lt": "lt", "le": "le", "gt": "gt", "ge": "ge", "eq": "eq", "ne": "ne",
    "select_n": "select_n",
    "and": "and", "or": "or", "xor": "xor", "not": "not",
    "shift_left": "shl", "shift_right_arithmetic": "shra",
    "shift_right_logical": "shrl",
    "reduce_sum": "reduce_sum", "reduce_max": "reduce_max",
    "reduce_min": "reduce_min",
    "broadcast_in_dim": "broadcast", "reshape": "reshape",
    "squeeze": "reshape", "transpose": "transpose", "rev": "rev",
    "slice": "slice", "gather": "gather", "concatenate": "concat",
    "pad": "pad", "iota": "iota", "convert_element_type": "convert",
    "dynamic_slice": "dynamic_slice",
    "dynamic_update_slice": "dynamic_update_slice",
    "device_put": "mov", "copy": "mov", "stop_gradient": "mov",
    "get": "ref_get", "swap": "ref_swap",
    "program_id": "program_id", "num_programs": "num_programs",
}

_CALL_PRIMS = CALL_PRIMS + (VJP_JAXPR_PRIM,)


class BuildError(ValueError):
    """The traced program is outside the IR's multiplierless contract."""


def _src(eqn) -> str:
    from repro.analysis.traverse import eqn_source
    return eqn_source(eqn)


def _dtype_code(dtype) -> str:
    d = np.dtype(dtype)
    if d.kind == "b":
        return "i1"
    if d == np.int32:
        return "i32"
    raise BuildError(
        f"dtype {d} is outside the int32 datapath carrier "
        "(the IR admits i32 values and i1 predicates only)")


def _shape_of(aval) -> tuple:
    return tuple(int(d) for d in getattr(aval, "shape", ()))


def _scalar_pow2_shift(val) -> object:
    """log2 of a positive-pow2 scalar/uniform literal, else None."""
    arr = np.ravel(np.asarray(val))
    if arr.size == 0:
        return None
    first = arr[0]
    if not np.all(arr == first):
        return None
    f = float(first)
    if f <= 0 or abs(math.log2(f) % 1.0) >= 1e-9:
        return None
    return int(round(math.log2(f)))


class _Builder:
    def __init__(self, records: dict):
        self.records = records        # (path, id(eqn)) -> RegisterRecord
        self.regs: list = []
        self.roms: list = []
        self.rom_of_reg: dict = {}
        self._const_cache: dict = {}  # (dtype, shape, bytes) -> reg idx
        self.has_grid = False
        self.grid_depth = 0

    # -- registers --------------------------------------------------------

    def new_reg(self, shape, dtype, rec=None) -> int:
        code = _dtype_code(dtype)
        bits = 1 if code == "i1" else 32
        interval = required = None
        if rec is not None and code != "i1":
            rb = rec.required_bits
            if not (isinstance(rb, float) and math.isinf(rb)):
                interval = (int(rec.lo), int(rec.hi))
                required = int(rb)
        self.regs.append(Reg(idx=len(self.regs), shape=shape, dtype=code,
                             bits=bits, interval=interval,
                             required_bits=required))
        return self.regs[-1].idx

    def const_reg(self, val, name: str) -> int:
        arr = np.asarray(val)
        if arr.dtype.kind == "b":
            data = arr.astype(np.bool_)
        elif arr.dtype.kind in ("i", "u") or (
                arr.dtype.kind == "f" and np.all(arr == np.trunc(arr))):
            # weak-typed scalar literals trace as f32 even in int programs;
            # an integral value is an int constant, a fractional one is not
            data = arr.astype(np.int64)
            if np.any(data > np.iinfo(np.int32).max) or \
                    np.any(data < np.iinfo(np.int32).min):
                raise BuildError(f"constant {name} exceeds int32")
            data = data.astype(np.int32)
        else:
            raise BuildError(
                f"constant {name} has non-integral float values — outside "
                "the int32 datapath")
        key = (data.dtype.str, data.shape, data.tobytes())
        hit = self._const_cache.get(key)
        if hit is not None:
            return hit
        ridx = len(self.roms)
        self.roms.append(Rom(idx=ridx, name=f"rom{ridx}_{name}", data=data))
        reg = self.new_reg(tuple(data.shape), data.dtype)
        self.rom_of_reg[reg] = ridx
        self._const_cache[key] = reg
        return reg

    # -- environment ------------------------------------------------------

    def _read(self, env, v) -> int:
        from jax._src.core import Literal
        if isinstance(v, Literal):
            return self.const_reg(v.val, "lit")
        return env[v]

    def _rec(self, path, eqn):
        return self.records.get((path, id(eqn)))

    def _bind_outs(self, eqn, env, path) -> tuple:
        rec = self._rec(path, eqn)
        outs = []
        for v in eqn.outvars:
            r = self.new_reg(_shape_of(v.aval),
                             getattr(v.aval, "dtype", np.bool_), rec)
            env[v] = r
            outs.append(r)
        return tuple(outs)

    @staticmethod
    def _census_elems(eqn) -> tuple:
        out = 0
        for v in eqn.outvars:
            n = 1
            for d in _shape_of(v.aval):
                n *= d
            out += n
        first = 1
        for d in _shape_of(eqn.invars[0].aval) if eqn.invars else ():
            first *= d
        return out, first

    # -- lowering ---------------------------------------------------------

    def lower_closed(self, closed, in_regs, path, stream) -> list:
        consts = [self.const_reg(c, "c") for c in closed.consts]
        return self.lower_jaxpr(closed.jaxpr, consts + list(in_regs),
                                path, stream)

    def lower_jaxpr(self, jaxpr, in_regs, path, stream) -> list:
        env = {}
        allvars = list(jaxpr.constvars) + list(jaxpr.invars)
        if len(allvars) != len(in_regs):
            raise BuildError(f"arity mismatch at {path or '<top>'}")
        for v, r in zip(allvars, in_regs):
            env[v] = r
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _CALL_PRIMS:
                self._lower_call(eqn, env, path, stream)
            elif name == "scan":
                self._lower_scan(eqn, env, path, stream)
            elif name == "pallas_call":
                self._lower_pallas(eqn, env, path, stream)
            elif name == "cond":
                # ``pl.when`` predication inside a grid kernel: legal as a
                # predicated region (hardware enable signal). The census
                # skips the branches — exactly the jaxpr census's
                # ``cond_branches=False`` semantics — while the analysis
                # verification passes already recurse into them.
                if self.grid_depth == 0:
                    raise BuildError(
                        f"cond at {path}/{_src(eqn)} outside a grid "
                        "region has no IR lowering")
                self._lower_cond(eqn, env, path, stream)
            elif name in ("while", "scatter", "scatter-add",
                          "dot_general", "conv_general_dilated"):
                raise BuildError(
                    f"{name} at {path}/{_src(eqn)} has no IR lowering — "
                    "the deployed integer datapath must not contain it")
            elif name == "mul":
                self._lower_mul(eqn, env, path, stream)
            else:
                self._lower_leaf(eqn, env, path, stream)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _lower_call(self, eqn, env, path, stream) -> None:
        closed = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                  or eqn.params.get("fun_jaxpr"))
        ins = [self._read(env, v) for v in eqn.invars]
        sub = f"{path}/{eqn.primitive.name}"
        if hasattr(closed, "consts"):
            outs = self.lower_closed(closed, ins, sub, stream)
        else:
            outs = self.lower_jaxpr(closed, ins, sub, stream)
        # inlined: sub-jaxpr outputs alias straight into this scope
        for v, r in zip(eqn.outvars, outs):
            env[v] = r

    def _lower_mul(self, eqn, env, path, stream) -> None:
        from jax._src.core import Literal
        lits = [v for v in eqn.invars if isinstance(v, Literal)]
        others = [v for v in eqn.invars if not isinstance(v, Literal)]
        k = _scalar_pow2_shift(lits[0].val) if len(lits) == 1 else None
        if k is None or len(others) != 1:
            raise BuildError(
                f"mul at {path}/{_src(eqn)} is not a positive-pow2-literal "
                "scaling — a real multiplier cannot be lowered to the "
                "multiplierless IR")
        x = self._read(env, others[0])
        out, first = self._census_elems(eqn)
        dests = self._bind_outs(eqn, env, path)
        stream.append(Instr(op="shl", dests=dests, srcs=(x,),
                            attrs={"imm": k}, jax_prim="mul",
                            census_out_elems=out, census_in_elems=first))

    def _lower_scan(self, eqn, env, path, stream) -> None:
        p = eqn.params
        closed = p["jaxpr"]
        length = p.get("length")
        length = 1 if length is None else int(length)
        n_consts, n_carry = int(p["num_consts"]), int(p["num_carry"])
        ins = [self._read(env, v) for v in eqn.invars]
        spath = f"{path}/scan[{length}]"

        body_consts = [self.const_reg(c, "c") for c in closed.consts]
        body_ins = [self.new_reg(_shape_of(v.aval),
                                 getattr(v.aval, "dtype", np.bool_))
                    for v in closed.jaxpr.invars]
        body_stream: list = []
        body_outs = self.lower_jaxpr(closed.jaxpr, body_consts + body_ins,
                                     spath, body_stream)
        region = Region(kind="loop", trip_count=length,
                        inputs=tuple(body_ins), outputs=tuple(body_outs),
                        body=body_stream,
                        attrs={"num_consts": n_consts, "num_carry": n_carry,
                               "reverse": bool(p.get("reverse", False))})
        dests = self._bind_outs(eqn, env, path)
        out, first = self._census_elems(eqn)
        stream.append(Instr(op="loop", dests=dests, srcs=tuple(ins),
                            attrs={"num_consts": n_consts,
                                   "num_carry": n_carry, "length": length},
                            regions=(region,), jax_prim="scan",
                            census_out_elems=out, census_in_elems=first))

    def _lower_cond(self, eqn, env, path, stream) -> None:
        ins = [self._read(env, v) for v in eqn.invars]
        regions = []
        for i, br in enumerate(eqn.params["branches"]):
            bpath = f"{path}/cond.branch{i}"
            body_consts = [self.const_reg(c, "c") for c in br.consts]
            body_ins = [self.new_reg(_shape_of(v.aval),
                                     getattr(v.aval, "dtype", np.bool_))
                        for v in br.jaxpr.invars]
            body_stream: list = []
            body_outs = self.lower_jaxpr(br.jaxpr, body_consts + body_ins,
                                         bpath, body_stream)
            regions.append(Region(kind="branch", trip_count=1,
                                  inputs=tuple(body_ins),
                                  outputs=tuple(body_outs),
                                  body=body_stream))
        dests = self._bind_outs(eqn, env, path)
        out, first = self._census_elems(eqn)
        stream.append(Instr(op="cond", dests=dests, srcs=tuple(ins),
                            attrs={}, regions=tuple(regions),
                            jax_prim="cond",
                            census_out_elems=out, census_in_elems=first))

    def _lower_pallas(self, eqn, env, path, stream) -> None:
        from repro.analysis.traverse import grid_product
        self.has_grid = True
        self.grid_depth += 1
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
        ppath = f"{path}/pallas_call"
        cells = [self.new_reg(_shape_of(v.aval),
                              getattr(v.aval, "dtype", np.int32))
                 for v in inner.invars]
        body_stream: list = []
        self.lower_jaxpr(inner, cells, ppath, body_stream)
        self.grid_depth -= 1
        region = Region(kind="grid", trip_count=grid_product(eqn),
                        inputs=tuple(cells), outputs=(), body=body_stream,
                        attrs={"grid": list(grid), "num_index": n_index,
                               "num_inputs": n_inputs,
                               "num_outputs": n_outputs})
        dests = self._bind_outs(eqn, env, path)
        out, first = self._census_elems(eqn)
        stream.append(Instr(op="grid", dests=dests, srcs=tuple(ins),
                            attrs=dict(region.attrs), regions=(region,),
                            jax_prim="pallas_call",
                            census_out_elems=out, census_in_elems=first))

    _ATTR_KEYS = {
        "slice": ("start_indices", "limit_indices", "strides"),
        "broadcast_in_dim": ("shape", "broadcast_dimensions"),
        "transpose": ("permutation",),
        "rev": ("dimensions",),
        "concatenate": ("dimension",),
        "pad": ("padding_config",),
        "dynamic_slice": ("slice_sizes",),
        "reduce_sum": ("axes",), "reduce_max": ("axes",),
        "reduce_min": ("axes",),
        "iota": ("shape", "dimension"),
        "program_id": ("axis",), "num_programs": ("axis",),
    }

    def _lower_leaf(self, eqn, env, path, stream) -> None:
        from jax._src.core import Literal
        name = eqn.primitive.name
        op = _DIRECT.get(name)
        if op is None:
            raise BuildError(
                f"primitive {name} at {path}/{_src(eqn)} is outside the "
                "multiplierless IR instruction set")

        attrs: dict = {}
        srcs = [self._read(env, v) for v in eqn.invars]
        for k in self._ATTR_KEYS.get(name, ()):
            val = eqn.params.get(k)
            if val is not None:
                attrs[k] = _plain(val)
        if name == "slice" and eqn.params.get("strides") is None:
            attrs["strides"] = [1] * len(attrs["start_indices"])
        if name in ("reshape", "squeeze"):
            attrs["new_shape"] = list(_shape_of(eqn.outvars[0].aval))
        if name == "convert_element_type":
            attrs["to"] = _dtype_code(eqn.params["new_dtype"])
        if name == "gather":
            dn = eqn.params["dimension_numbers"]
            attrs.update(
                offset_dims=list(dn.offset_dims),
                collapsed_slice_dims=list(dn.collapsed_slice_dims),
                start_index_map=list(dn.start_index_map),
                operand_batching_dims=list(
                    getattr(dn, "operand_batching_dims", ()) or ()),
                start_indices_batching_dims=list(
                    getattr(dn, "start_indices_batching_dims", ()) or ()),
                slice_sizes=list(eqn.params["slice_sizes"]))
        if name in ("get", "swap"):
            attrs["tree"] = str(eqn.params.get("tree"))
        # fold literal scalar shift amounts into an immediate (the shifter
        # the netlist instantiates is constant-distance when the program is)
        if name in ("shift_left", "shift_right_arithmetic",
                    "shift_right_logical") and len(eqn.invars) == 2 \
                and isinstance(eqn.invars[1], Literal) \
                and np.ndim(eqn.invars[1].val) == 0:
            attrs["imm"] = int(eqn.invars[1].val)
            srcs = srcs[:1]

        out, first = self._census_elems(eqn)
        dests = self._bind_outs(eqn, env, path)
        stream.append(Instr(op=op, dests=dests, srcs=tuple(srcs),
                            attrs=attrs, jax_prim=name,
                            census_out_elems=out, census_in_elems=first))


def _plain(v):
    """Static param -> JSON-serializable plain value."""
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, (np.integer, np.bool_)):
        return int(v)
    return v


class _InputRec:
    """Record-shaped view of a declared input interval (ducks the interval
    pass's RegisterRecord for ``_Builder.new_reg``)."""

    def __init__(self, lo, hi, required_bits):
        self.lo, self.hi, self.required_bits = lo, hi, required_bits


def build_program(closed_jaxpr, *, name: str, in_intervals=None,
                  scan_unroll_limit: int = 64,
                  grid_unroll_limit: int = 4096) -> Program:
    """Lower a traced ``ClosedJaxpr`` into a typed IR :class:`Program`.

    With ``in_intervals`` (one Interval per flattened input, as in
    ``repro.analysis.targets``) the worst-case interval pass runs over the
    same jaxpr first and every register is typed with its PROVEN interval
    and minimal two's-complement width. Without it registers carry only
    shapes and carrier widths.
    """
    records: dict = {}
    interval_meta: dict = {}
    if in_intervals is not None:
        from repro.analysis.intervals import analyze_intervals
        res = analyze_intervals(closed_jaxpr, in_intervals,
                                scan_unroll_limit=scan_unroll_limit,
                                grid_unroll_limit=grid_unroll_limit)
        records = res.records_by_eqn
        interval_meta = {
            "interval_ok": bool(res.ok),
            "min_headroom_bits": (None if isinstance(res.min_headroom_bits,
                                                     float)
                                  else int(res.min_headroom_bits)),
            "max_required_bits": (None if isinstance(res.max_required_bits,
                                                     float)
                                  else int(res.max_required_bits)),
        }

    b = _Builder(records)
    jaxpr = closed_jaxpr.jaxpr
    # input registers are typed straight from the DECLARED intervals (the
    # interval pass records only equation outputs): the netlist register
    # allocator sees the ADC input ports at their true width, not int32
    in_recs: list = [None] * len(jaxpr.invars)
    if in_intervals is not None:
        from repro.analysis.intervals import carrier_bits
        for i, iv in enumerate(list(in_intervals)[:len(in_recs)]):
            in_recs[i] = _InputRec(lo=iv.lo, hi=iv.hi,
                                   required_bits=carrier_bits(iv))
    in_regs = [b.new_reg(_shape_of(v.aval),
                         getattr(v.aval, "dtype", np.int32), in_recs[i])
               for i, v in enumerate(jaxpr.invars)]
    stream: list = []
    const_regs = [b.const_reg(c, "c") for c in closed_jaxpr.consts]
    outs = b.lower_jaxpr(jaxpr, const_regs + in_regs, "", stream)
    meta = {"num_instrs": None, "rom_bytes": None}
    meta.update(interval_meta)
    prog = Program(name=name, inputs=tuple(in_regs), outputs=tuple(outs),
                   regs=b.regs, roms=b.roms, rom_of_reg=b.rom_of_reg,
                   body=stream, meta=meta, executable=not b.has_grid)
    prog.meta["num_instrs"] = prog.num_instrs()
    prog.meta["rom_bytes"] = prog.rom_bytes()
    return prog
