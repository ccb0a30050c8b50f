"""Property tests for the ``core.derived`` formula evaluator.

The evaluator is the user-programmable surface of the viewer (§4.5/§7.1
spreadsheet formulas), so its contract must be *total*: any well-formed
formula over any finite/NaN metric columns evaluates without raising,
division by zero yields 0 (the hpcviewer convention), and the usual
algebraic identities hold on the sparse columns.

Strategies build random well-formed formula trees from the grammar the
evaluator accepts (names, constants, + - * /, unary minus, whitelisted
calls, comparisons, conditional expressions) together with matching
random columns.  Guarded via tests/hypothesis_compat.py: without
hypothesis installed these are reported as skips, never errors.
"""
import math

import numpy as np
import pytest
from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core.derived import DerivedMetric, sanitize

NAMES = ("a", "b", "c")


def _exprs():
    """Random well-formed formula strings over NAMES."""
    atoms = st.one_of(
        st.sampled_from(NAMES),
        st.floats(-1e6, 1e6, allow_nan=False,
                  allow_infinity=False).map(lambda v: repr(round(v, 3))),
    )

    def compound(inner):
        bins = st.tuples(inner, st.sampled_from([" + ", " - ", " * ",
                                                 " / "]), inner) \
            .map(lambda t: f"({t[0]}{t[1]}{t[2]})")
        neg = inner.map(lambda e: f"(-{e})")
        calls = st.tuples(st.sampled_from(["abs", "sqrt", "log", "exp"]),
                          inner).map(lambda t: f"{t[0]}({t[1]})")
        two = st.tuples(st.sampled_from(["min", "max"]), inner, inner) \
            .map(lambda t: f"{t[0]}({t[1]}, {t[2]})")
        cond = st.tuples(inner, st.sampled_from([" > ", " <= ", " == "]),
                         inner, inner, inner) \
            .map(lambda t: f"({t[3]} if {t[0]}{t[1]}{t[2]} else {t[4]})")
        return st.one_of(bins, neg, calls, two, cond)

    return st.recursive(atoms, compound, max_leaves=12)


def _columns():
    # bounded finite values plus NaN, drawn apart: hypothesis rejects
    # allow_nan=True together with bounds
    vals = st.one_of(st.floats(-1e9, 1e9, allow_nan=False,
                               allow_infinity=False, width=64),
                     st.just(math.nan))
    return st.integers(1, 6).flatmap(
        lambda n: st.fixed_dictionaries(
            {name: st.lists(vals, min_size=n, max_size=n).map(np.array)
             for name in NAMES}))


@given(_exprs(), _columns())
@settings(max_examples=150, deadline=None)
def test_evaluation_is_total(expr, cols):
    """Any well-formed formula evaluates on any columns: no exception,
    result broadcastable to the column shape."""
    m = DerivedMetric("p", expr)
    with np.errstate(all="ignore"):
        out = np.asarray(m.evaluate(cols), dtype=np.float64)
    n = len(next(iter(cols.values())))
    assert out.shape in ((), (n,))


@given(_columns())
@settings(max_examples=100, deadline=None)
def test_zero_division_policy_total(cols):
    """x / 0 == 0 elementwise — including 0/0 — and never raises."""
    a = np.nan_to_num(cols["a"])
    b = np.nan_to_num(cols["b"])
    out = DerivedMetric("q", "a / b").evaluate({"a": a, "b": b})
    expect = np.where(b != 0, np.divide(a, np.where(b != 0, b, 1)), 0.0)
    np.testing.assert_array_equal(out, expect)
    # the zero-divisor lanes specifically are exactly 0, not inf/NaN
    assert (np.asarray(out)[b == 0] == 0.0).all()


@given(_columns())
@settings(max_examples=100, deadline=None)
def test_algebraic_identities(cols):
    """Commutativity holds exactly (FP + and * are commutative), and
    a - a is identically 0 on finite columns."""
    finite = {k: np.nan_to_num(v) for k, v in cols.items()}
    with np.errstate(all="ignore"):
        ab = DerivedMetric("x", "a + b").evaluate(finite)
        ba = DerivedMetric("x", "b + a").evaluate(finite)
        np.testing.assert_array_equal(ab, ba)
        mul_ab = DerivedMetric("x", "a * b").evaluate(finite)
        mul_ba = DerivedMetric("x", "b * a").evaluate(finite)
        np.testing.assert_array_equal(mul_ab, mul_ba)
        zero = DerivedMetric("x", "a - a").evaluate(finite)
    np.testing.assert_array_equal(zero, np.zeros_like(finite["a"]))


@given(_exprs())
@settings(max_examples=100, deadline=None)
def test_roundtrip_reparse(expr):
    """Accepted formulas stay accepted (the validator is stable) and
    evaluate identically when re-parsed."""
    m1 = DerivedMetric("r", expr)
    m2 = DerivedMetric("r", m1.formula)
    cols = {n: np.array([1.5, -2.0, 0.0]) for n in NAMES}
    with np.errstate(all="ignore"):
        np.testing.assert_array_equal(
            np.asarray(m1.evaluate(cols), np.float64),
            np.asarray(m2.evaluate(cols), np.float64))


def test_sanitize_is_injective_on_metric_names():
    """Sanitized names of all default metrics stay distinct (a collision
    would silently alias two columns in every formula)."""
    from repro.core.metrics import default_registry
    names = default_registry().metric_names
    out = [sanitize(n) for n in names]
    assert len(set(out)) == len(names)


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="hypothesis not installed (see pyproject [test])")
def test_property_suite_is_active():
    """Guard: when hypothesis IS available the property tests above must
    actually run (they skip silently otherwise by design)."""
    assert HAVE_HYPOTHESIS
