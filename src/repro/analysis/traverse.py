"""The shared jaxpr traversal every analysis pass runs on.

A traced program is a tree of jaxprs: the top level plus sub-jaxprs hidden
inside higher-order primitives (``jit``/call wrappers, ``scan``/``while``
loops, ``cond`` branches, ``pallas_call`` kernel bodies). Every pass in
this package — and the benchmark census in ``benchmarks/hardware_cost.py``
— walks that tree through ONE function (:func:`walk`), so the legality
gate, the census numbers and the lint can never disagree about what code a
program contains.

The walk is *scaled*: each visited equation carries the number of times it
executes per call (scan length x pallas grid product x ...), which is what
turns a structural walk into an op census.

Census-compatibility quirks (kept deliberately, flag-controlled):

* ``cond`` branches execute at most once each but the pre-refactor census
  skipped them entirely; counting passes keep that behavior
  (``cond_branches=False``) so benchmark trajectories stay comparable,
  while verification passes recurse (``cond_branches=True``) — the gate is
  strictly stronger than the numbers.
* ``while`` bodies have no static trip count. The census skips them
  (nothing in the repo's datapath uses ``while``); verification passes
  visit the body once at the current scale — sound for legality (an
  illegal op is illegal at any trip count), not a count.

``pallas_call`` index-map jaxprs (BlockSpec address arithmetic) are NOT
walked: they compute grid offsets on the scalar core, not datapath values.
"""

from __future__ import annotations

from typing import Callable, Iterator

# call-like primitives whose sub-jaxpr runs exactly once per invocation
CALL_PRIMS = ("jit", "closed_call", "custom_vjp_call", "custom_jvp_call",
              "remat", "checkpoint")

# jax 0.4.x names the staged-out custom-vjp primitive differently; the
# pre-refactor census treated it as an opaque leaf (counted nothing), so
# counting passes keep that behavior behind ``vjp_jaxpr_bodies`` while
# verification passes recurse into the body
VJP_JAXPR_PRIM = "custom_vjp_call_jaxpr"


def subjaxprs(value) -> Iterator:
    """Yield every plain jaxpr reachable from a param value: handles plain
    ``Jaxpr`` (has ``.eqns``), ``ClosedJaxpr`` (has ``.jaxpr``), and
    lists/tuples of either — ``pallas_call`` stores a plain ``Jaxpr``,
    ``cond`` a tuple of ``ClosedJaxpr``, so attribute order matters."""
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr"):
        yield from subjaxprs(value.jaxpr)
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from subjaxprs(v)


def grid_product(eqn) -> int:
    """Number of sequential kernel-body executions of a ``pallas_call``:
    the product of the static grid dimensions."""
    gm = eqn.params.get("grid_mapping")
    steps = 1
    for g in getattr(gm, "grid", ()) or ():
        if isinstance(g, int):
            steps *= g
    return steps


def eqn_source(eqn) -> str:
    """Human-readable source location of an equation (for naming offending
    eqns in reports): ``file.py:123 (fn_name)`` when available."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            fname = frame.file_name.rsplit("/", 1)[-1]
            return f"{fname}:{frame.start_line} ({frame.function_name})"
    except Exception:  # noqa: BLE001 - source info is best-effort decoration
        pass
    return "<unknown>"


def walk(jaxpr, visit: Callable, *, scale: int = 1, path: str = "",
         cond_branches: bool = True, while_bodies: bool = True,
         vjp_jaxpr_bodies: bool = True) -> None:
    """Visit every leaf equation reachable from ``jaxpr``.

    ``visit(eqn, scale, path)`` is called for each non-higher-order
    equation; ``scale`` is how many times it executes per program call and
    ``path`` names the enclosing higher-order chain (for report naming).
    Higher-order primitives are recursed per the module docstring;
    ``cond_branches``/``while_bodies``/``vjp_jaxpr_bodies`` select
    verification vs census semantics.
    """
    kw = dict(cond_branches=cond_branches, while_bodies=while_bodies,
              vjp_jaxpr_bodies=vjp_jaxpr_bodies)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in CALL_PRIMS or name == VJP_JAXPR_PRIM:
            if name == VJP_JAXPR_PRIM and not vjp_jaxpr_bodies:
                continue
            for sub in eqn.params.values():
                for jx in subjaxprs(sub):
                    walk(jx, visit, scale=scale, path=path, **kw)
            continue
        if name == "pallas_call":
            steps = grid_product(eqn)
            for jx in subjaxprs(eqn.params.get("jaxpr")):
                walk(jx, visit, scale=scale * steps,
                     path=f"{path}/pallas_call[grid={steps}]", **kw)
            continue
        if name == "scan":
            # a zero-length scan's body executes zero times: scale 0 keeps
            # counts exact (the visit still happens, so legality stays
            # conservative about code that is merely never reached)
            length = eqn.params.get("length")
            length = 1 if length is None else int(length)
            for jx in subjaxprs(eqn.params.get("jaxpr")):
                walk(jx, visit, scale=scale * length,
                     path=f"{path}/scan[{length}]", **kw)
            continue
        if name == "while":
            if while_bodies:
                for key in ("cond_jaxpr", "body_jaxpr"):
                    for jx in subjaxprs(eqn.params.get(key)):
                        walk(jx, visit, scale=scale,
                             path=f"{path}/while.{key}", **kw)
            continue
        if name == "cond":
            if cond_branches:
                for i, br in enumerate(eqn.params.get("branches", ())):
                    for jx in subjaxprs(br):
                        walk(jx, visit, scale=scale,
                             path=f"{path}/cond.branch{i}", **kw)
            continue
        visit(eqn, scale, path)
