"""Static analysis over traced jaxprs: the verification layer for the
multiplierless datapath.

The paper's hardware claim (0 DSPs, <1K slices) is a claim about the
*deployed representation*: every primitive is an add/sub/shift/compare and
every register fits its declared bitwidth. This package proves both
properties on the traced integer programs instead of sampling them:

``traverse``
    One shared jaxpr walk (recursing through ``jit``, ``scan``, ``cond``,
    ``while``, ``pallas_call`` and friends) that every pass — and the
    benchmark census — runs on, so the gate and the numbers can't diverge.
``legality``
    Op-legality pass (the generalized multiplierless verifier) plus the
    compatibility census that ``benchmarks/hardware_cost.py`` re-exports.
``intervals``
    Worst-case interval analysis: abstract interpretation from the ADC
    range through FIR partials, HWR accumulators and the MP bisection,
    proving every intermediate fits its integer dtype for ANY input and
    reporting per-register required bitwidths.
``determinism``
    Lint for bit-parity hazards: non-fixed-tree float reductions and float
    ops reachable in a ``numerics="fixed"`` program.
``targets``
    The standard analysis targets (one-shot ``infer_q``, per-chunk
    ``session_step_q``, both int Pallas kernels) with their documented
    input assumptions.
``report``
    Machine-readable report assembly for ``scripts/analyze.py``.
"""

from repro.analysis.legality import (  # noqa: F401
    CensusCounter,
    assert_multiplierless,
    census,
    census_jaxpr,
    check_legality,
    literal_pow2_multiplicand,
)
from repro.analysis.intervals import (  # noqa: F401
    Interval,
    IntervalResult,
    analyze_intervals,
)
from repro.analysis.determinism import lint_determinism  # noqa: F401
from repro.analysis.traverse import subjaxprs, walk  # noqa: F401
