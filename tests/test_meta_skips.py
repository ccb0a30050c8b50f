"""Meta-test: the skip inventory is frozen (ISSUE 3 test sweep).

Audit result (2026-07, re-audited for ISSUE 4): every skip in this
suite is *environment-dependent* — there is nothing to convert to a
running test or xfail:

- ``hypothesis_compat.py`` marks ``@given`` property tests skipped only
  when the optional ``hypothesis`` package is absent (they run in CI,
  which installs ``.[test]``).  ISSUE 4's merge-algebra properties
  (``test_merge_properties.py``) ride this same single guard — no new
  skip *mechanism* — and pin a no-hypothesis fallback by running the
  property bodies on a fixed example
  (``test_properties_hold_on_fixed_example``);
- ``test_structure.py`` skips one assertion block only on jax builds
  that emit no ``StackFrames`` metadata table;
- ``test_counters.py`` module-skips only when jax itself is absent
  (the analysis half of the suite stays importable without jax);
- ``test_goldens.py`` skips only under the explicit opt-in
  ``--update-goldens`` flag (the "test" then rewrites its golden; the
  ISSUE 4 merge-CLI golden reuses the same helper, so it adds no skip
  site either);
- ``test_derived_properties.py`` carries one ``skipif`` guard asserting
  the property suite is active whenever hypothesis is present;
- ``test_tpu_compile.py`` skips only where libtpu cannot describe a
  v5e topology to compile for.

This test freezes that inventory at the *source* level: any new
``skip`` / ``skipif`` / ``importorskip`` / ``xfail`` use anywhere in
``tests/`` fails here until it is added to the allowlist below with a
justification — so the skip count can never grow silently.
"""
import io
import os
import re
import tokenize

TESTS_DIR = os.path.dirname(__file__)

# (filename, mechanism) -> expected occurrence count, with why it is
# environment-dependent (or explicitly opted into).
ALLOWED_SKIPS = {
    ("hypothesis_compat.py", "pytest.mark.skip"): 1,   # hypothesis absent
    ("test_structure.py", "pytest.skip"): 1,           # no StackFrames table
    ("test_counters.py", "pytest.importorskip"): 1,    # jax absent
    ("test_kstruct.py", "pytest.importorskip"): 1,     # jax absent (the
    # structure-recovery half traces real Pallas kernels via make_jaxpr;
    # same guard as test_counters.py, no new mechanism)
    ("test_goldens.py", "pytest.skip"): 1,             # --update-goldens
    ("test_derived_properties.py", "pytest.mark.skipif"): 1,  # guard-guard
    ("test_tpu_compile.py", "pytest.skip"): 1,         # no libtpu to
    # describe a v5e topology with (skipped from a fixture, never at
    # import: only one process may load libtpu)
}

_MECHANISMS = (
    "pytest.importorskip",
    "pytest.mark.skipif",
    "pytest.mark.skip",
    "pytest.mark.xfail",
    "pytest.skip",
    "pytest.xfail",
)


def _code_text(path: str) -> str:
    """Source with string literals and comments dropped (tokenize-based),
    so docstrings that merely *mention* a mechanism never count."""
    out = []
    with open(path, "rb") as f:
        for tok in tokenize.tokenize(f.readline):
            if tok.type in (tokenize.STRING, tokenize.COMMENT):
                out.append(" ")
            elif tok.type == tokenize.NAME or tok.type == tokenize.OP:
                out.append(tok.string)
            else:
                out.append(" ")
    return " ".join(out)


def _scan():
    found = {}
    for fn in sorted(os.listdir(TESTS_DIR)):
        # this file only names mechanisms in strings/keys, but exclude it
        # anyway: it is the scanner, not a skip site
        if not fn.endswith(".py") or fn == os.path.basename(__file__):
            continue
        code = _code_text(os.path.join(TESTS_DIR, fn))
        for mech in _MECHANISMS:
            # any code-position reference counts — called OR a bare
            # ``@pytest.mark.skip`` decorator (valid pytest without
            # parens); the lookahead keeps the attribute name exact, so
            # ``pytest.mark.skip`` never also counts ``skipif`` sites
            pat = r"\s*\.\s*".join(re.escape(p) for p in mech.split(".")) \
                + r"(?![A-Za-z0-9_])"
            n = len(re.findall(pat, code))
            if n:
                found[(fn, mech)] = n
    return found


def test_skip_inventory_is_frozen():
    found = _scan()
    expected = dict(ALLOWED_SKIPS)
    assert found == expected, (
        "skip mechanisms changed.\n"
        f"  found:    {sorted(found.items())}\n"
        f"  expected: {sorted(expected.items())}\n"
        "New skips must be environment-dependent and added to "
        "ALLOWED_SKIPS in tests/test_meta_skips.py with a justification; "
        "environment-independent skips should be running tests or loud "
        "xfail(reason=...) instead.")


def test_meta_scanner_excludes_this_file():
    """The scanner must not trip on this file's own allowlist strings
    (they are never followed by an open paren)."""
    found = _scan()
    assert not any(fn == "test_meta_skips.py" for fn, _ in found)


def test_hypothesis_guard_is_the_only_hypothesis_import():
    """All property tests must go through hypothesis_compat so a missing
    hypothesis degrades to per-test skips, never collection errors."""
    offenders = []
    for fn in sorted(os.listdir(TESTS_DIR)):
        if not fn.endswith(".py") or fn == "hypothesis_compat.py":
            continue
        with open(os.path.join(TESTS_DIR, fn)) as f:
            for line in f:
                if re.match(r"\s*(from|import)\s+hypothesis\b", line):
                    offenders.append(fn)
    assert not offenders, \
        f"import hypothesis via tests/hypothesis_compat.py: {offenders}"
