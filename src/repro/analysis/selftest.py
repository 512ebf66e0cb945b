"""scopelint self-test: prove every rule alive before trusting a clean run.

A static checker's worst failure mode is silence — a refactor that makes a
rule stop matching produces the same output as a healthy codebase.  So
every rule ships a corpus: ``triggers`` it must flag and ``non_triggers``
(near-identical twins) it must not.  The suppression machinery and the
jaxpr walker get the same treatment: a deliberately-poisoned toy jit
(host callback + f64 promotion) must be flagged, a clean one must not.

``run_self_test()`` returns failure messages; empty means healthy.
"""
from __future__ import annotations

from typing import List

_SELFTEST_PATH = "repro/serving/_scopelint_selftest.py"   # forces hot-path

_SUPPRESSED_SNIPPET = """\
import jax


@jax.jit
def f(x):
    return float(x)  # scopelint: allow[host-sync-in-hot-path] -- corpus
"""

_UNSUPPRESSED_TWIN = _SUPPRESSED_SNIPPET.replace(
    "  # scopelint: allow[host-sync-in-hot-path] -- corpus", "")


def run_self_test() -> List[str]:
    from repro.analysis.astpass import ModuleContext
    from repro.analysis.runner import all_rules, scan_source

    failures: List[str] = []
    for rule in all_rules():
        for i, snip in enumerate(rule.triggers):
            ctx = ModuleContext(snip, _SELFTEST_PATH, hot_path=True)
            hits = list(rule.check(ctx))
            if not hits:
                failures.append(
                    f"{rule.id}: trigger snippet #{i} produced no finding")
        for i, snip in enumerate(rule.non_triggers):
            ctx = ModuleContext(snip, _SELFTEST_PATH, hot_path=True)
            hits = list(rule.check(ctx))
            if hits:
                failures.append(
                    f"{rule.id}: non-trigger snippet #{i} false-positived: "
                    f"{hits[0].message!r}")

    # suppression machinery: the allow comment must absorb the finding...
    sup = scan_source(_SUPPRESSED_SNIPPET, _SELFTEST_PATH, hot_path=True)
    if [f for f in sup if not f.suppressed]:
        failures.append("suppression: allow[...] comment did not suppress")
    if not [f for f in sup if f.suppressed]:
        failures.append("suppression: suppressed finding not reported")
    # ...and the twin without it must fail
    raw = scan_source(_UNSUPPRESSED_TWIN, _SELFTEST_PATH, hot_path=True)
    if not [f for f in raw if not f.suppressed]:
        failures.append("suppression: unsuppressed twin produced no finding")

    failures.extend(_jaxpr_self_test())
    return failures


def _jaxpr_self_test() -> List[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.jaxpr_pass import check_closed_jaxpr

    failures: List[str] = []
    x = jax.ShapeDtypeStruct((4,), jnp.float32)

    def poisoned(v):
        y = jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(v.shape, v.dtype), v)
        return y.astype(jnp.float64)

    # enable_x64 scoped to the trace so the f64 survives canonicalisation
    with jax.enable_x64(True):
        bad = jax.make_jaxpr(poisoned)(x)
    msgs = " ".join(f.message for f in check_closed_jaxpr("poisoned", bad))
    if "pure_callback" not in msgs:
        failures.append("jaxpr: poisoned toy jit's host callback missed")
    if "float64" not in msgs:
        failures.append("jaxpr: poisoned toy jit's f64 promotion missed")

    clean = jax.make_jaxpr(lambda v: (v * 2.0).sum())(x)
    leftover = check_closed_jaxpr("clean", clean)
    if leftover:
        failures.append(
            f"jaxpr: clean toy jit false-positived: {leftover[0].message!r}")
    return failures
