"""scopelint (repro.analysis): rule corpus self-test, suppression parsing,
the jaxpr poison checks, and the kwonly-static regression that keeps the
Pallas kernels' partial-bound knobs from false-positiving."""
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import all_rules, scan_paths, scan_source
from repro.analysis.astpass import ModuleContext
from repro.analysis.jaxpr_pass import check_closed_jaxpr, run_jaxpr_pass
from repro.analysis.manifest import is_hot_path
from repro.analysis.selftest import run_self_test
from repro.analysis.suppress import MISSING_REASON, UNUSED, Suppressions

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Rule corpus: every rule fires on its triggers, stays silent on its twins
# ---------------------------------------------------------------------------
def test_self_test_corpus_is_green():
    assert run_self_test() == []


def test_every_rule_ships_a_corpus():
    for rule in all_rules():
        assert rule.triggers, f"{rule.id} has no trigger corpus"
        assert rule.non_triggers, f"{rule.id} has no non-trigger corpus"


def test_rule_ids_are_the_documented_five():
    assert sorted(r.id for r in all_rules()) == [
        "host-sync-in-hot-path", "pallas-kernel-contract",
        "recompile-hazard", "serve-time-nondeterminism",
        "traced-body-side-effect"]


# ---------------------------------------------------------------------------
# Suppression comments
# ---------------------------------------------------------------------------
def test_inline_suppression_absorbs_finding_and_keeps_reason():
    src = textwrap.dedent("""\
        import jax


        @jax.jit
        def f(x):
            return float(x)  # scopelint: allow[host-sync-in-hot-path] -- ok
        """)
    out = scan_source(src, "repro/serving/x.py", hot_path=True)
    assert out and all(f.suppressed for f in out)
    assert out[0].suppress_reason == "ok"
    # the same module without the waiver must fail
    raw = scan_source(src.replace(
        "  # scopelint: allow[host-sync-in-hot-path] -- ok", ""),
        "repro/serving/x.py", hot_path=True)
    assert any(not f.suppressed for f in raw)


def test_standalone_suppression_targets_next_line_and_star_matches():
    sup = Suppressions.parse(
        "# scopelint: allow[*] -- blanket\n"
        "x = 1\n")
    assert sup.match("any-rule-at-all", 2) is not None
    assert sup.match("another", 1) is None  # the comment's own line


def test_suppression_without_reason_is_itself_a_finding():
    sup = Suppressions.parse("x = 1  # scopelint: allow[recompile-hazard]\n")
    sup.match("recompile-hazard", 1)
    metas = sup.meta_findings("p.py")
    assert [m.rule for m in metas] == [MISSING_REASON]


def test_unused_suppression_is_itself_a_finding():
    sup = Suppressions.parse("x = 1  # scopelint: allow[recompile-hazard] -- r\n")
    metas = sup.meta_findings("p.py")
    assert [m.rule for m in metas] == [UNUSED]


def test_meta_findings_cannot_be_suppressed():
    sup = Suppressions.parse(
        "x = 1  # scopelint: allow[unused-suppression] -- nice try\n")
    assert sup.match(UNUSED, 1) is None
    assert sup.match(MISSING_REASON, 1) is None


def test_docstring_mention_of_syntax_is_not_a_waiver():
    src = '"""Docs: use # scopelint: allow[rule] -- reason to waive."""\n'
    sup = Suppressions.parse(src)
    assert sup.match("rule", 1) is None
    assert sup.meta_findings("p.py") == []


# ---------------------------------------------------------------------------
# Hot-path manifest
# ---------------------------------------------------------------------------
def test_hot_path_manifest():
    assert is_hot_path("src/repro/serving/sampler.py")
    assert is_hot_path("src/repro/kernels/decode_attention.py")
    assert is_hot_path("src/repro/api/engine.py")
    assert not is_hot_path("src/repro/api/cache.py")
    assert not is_hot_path("src/repro/training/grpo.py")
    assert not is_hot_path("tests/test_runtime.py")


# ---------------------------------------------------------------------------
# Kwonly-static regression: partial-bound kernel knobs are not traced
# ---------------------------------------------------------------------------
_KWONLY_KERNEL = textwrap.dedent("""\
    import functools

    import jax
    import jax.experimental.pallas as pl


    def _kernel(x_ref, o_ref, *, softcap):
        if softcap > 0.0:
            o_ref[...] = x_ref[...] / softcap
        else:
            o_ref[...] = x_ref[...]


    def run(x, softcap):
        kern = functools.partial(_kernel, softcap=float(softcap))
        return pl.pallas_call(
            kern, grid=(1,),
            in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0))],
            out_specs=pl.BlockSpec(x.shape, lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
    """)


def test_kwonly_kernel_param_is_static_not_traced():
    """`softcap` is bound via functools.partial before pallas_call, so the
    branch on it resolves at trace time — recompile-hazard must stay silent
    (this was a 6-site false positive on the real decode kernels)."""
    ctx = ModuleContext(_KWONLY_KERNEL, "repro/kernels/k.py", hot_path=True)
    from repro.analysis.rules_recompile import RecompileHazardRule
    assert list(RecompileHazardRule().check(ctx)) == []


def test_positional_kernel_param_branch_is_flagged():
    src = _KWONLY_KERNEL.replace(
        "def _kernel(x_ref, o_ref, *, softcap):",
        "def _kernel(x_ref, o_ref, softcap_ref):").replace(
        "if softcap > 0.0:", "if softcap_ref[0] > 0.0:").replace(
        "kern = functools.partial(_kernel, softcap=float(softcap))",
        "kern = functools.partial(_kernel)")
    ctx = ModuleContext(src, "repro/kernels/k.py", hot_path=True)
    from repro.analysis.rules_recompile import RecompileHazardRule
    hits = list(RecompileHazardRule().check(ctx))
    assert hits and hits[0].rule == "recompile-hazard"


# ---------------------------------------------------------------------------
# jaxpr pass
# ---------------------------------------------------------------------------
def test_jaxpr_pass_flags_poisoned_toy_jit():
    x = jax.ShapeDtypeStruct((4,), jnp.float32)

    def poisoned(v):
        y = jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(v.shape, v.dtype), v)
        return y.astype(jnp.float64)

    with jax.enable_x64(True):
        bad = jax.make_jaxpr(poisoned)(x)
    msgs = " ".join(f.message for f in check_closed_jaxpr("bad", bad))
    assert "pure_callback" in msgs and "float64" in msgs


def test_jaxpr_pass_passes_clean_toy_jit():
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    clean = jax.make_jaxpr(
        lambda v: jax.lax.scan(lambda c, t: (c + t, c), 0.0, v))(x)
    assert check_closed_jaxpr("clean", clean) == []


def test_jaxpr_pass_callback_inside_scan_body_is_found():
    """The walker must recurse into sub-jaxprs (scan bodies), where a
    callback would serialise every decode step."""
    x = jax.ShapeDtypeStruct((4,), jnp.float32)

    def body(c, t):
        t = jax.pure_callback(np.sin, jax.ShapeDtypeStruct((), t.dtype), t)
        return c + t, c

    bad = jax.make_jaxpr(lambda v: jax.lax.scan(body, 0.0, v))(x)
    msgs = " ".join(f.message for f in check_closed_jaxpr("scan", bad))
    assert "pure_callback" in msgs


def test_registered_hot_path_executables_are_clean():
    """Acceptance: fused decode, paged segment scan (both kernels) and the
    paged fused refill trace with abstract inputs and contain no host callbacks,
    f64 promotions, or staged host transfers."""
    findings = run_jaxpr_pass()
    assert findings == [], [f.render() for f in findings]


# ---------------------------------------------------------------------------
# The repo itself is clean (AST layer; the jaxpr layer is the test above)
# ---------------------------------------------------------------------------
def test_src_tree_has_no_unsuppressed_findings():
    findings = scan_paths([str(REPO / "src")])
    hard = [f for f in findings if not f.suppressed]
    assert hard == [], [f.render() for f in hard]
