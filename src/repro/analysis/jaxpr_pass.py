"""Layer 2: trace the registered hot-path executables and walk the jaxprs.

The AST rules see what the *source* says; this pass sees what *XLA* sees.
Each registered executable is traced with abstract inputs
(``jax.ShapeDtypeStruct`` leaves via ``jax.eval_shape`` /
``jax.make_jaxpr`` — no FLOPs, no device memory) and its closed jaxpr is
walked recursively (scan bodies, pjit sub-jaxprs, pallas kernels) for:

- **host callbacks** (``pure_callback`` / ``io_callback`` / debug
  callbacks / outfeed): a callback inside the fused decode scan would
  serialise every step on the host;
- **f64 promotions**: a ``convert_element_type`` to float64 (or any
  float64/complex128 intermediate) doubles KV bandwidth and silently
  disables TPU-native matmuls;
- **device-to-host transfers** staged into the computation
  (``device_put`` to a host memory kind).

Registry: ``register("name")(builder)`` where ``builder() -> ClosedJaxpr``.
The default registry covers the serve path's five jitted executables —
the dense one-shot decode (``_scan_decode``), the paged segment scan
(``_paged_scan_decode``, XLA and Pallas kernels), the paged fused refill
(``_paged_refill_scan_decode``), and the tier-0 pre-router forward
(``tier0_forward``) — built over the TINY estimator config.  A
builder that *fails to trace* is itself a finding: the hot path no longer
compiles, which is worse than any primitive it might contain.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List

from repro.analysis.findings import Finding

RULE_ID = "jaxpr-forbidden-primitive"

_CALLBACK_SUBSTR = ("callback", "outside_call", "infeed", "outfeed",
                    "host_local_array")
_WIDE_DTYPES = ("float64", "complex128")

_REGISTRY: Dict[str, Callable[[], Any]] = {}


def register(name: str):
    """Register a hot-path executable builder for the jaxpr pass."""
    def deco(builder: Callable[[], Any]):
        _REGISTRY[name] = builder
        return builder
    return deco


def registered() -> Dict[str, Callable[[], Any]]:
    _ensure_defaults()
    return dict(_REGISTRY)


def iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into sub-jaxprs in params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            yield from _iter_sub(val)


def _iter_sub(val):
    # sub-jaxprs appear as Jaxpr/ClosedJaxpr params, possibly nested in
    # containers (branches of cond/switch, pallas grid mappings)
    if hasattr(val, "eqns"):
        yield from iter_eqns(val)
    elif hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
        yield from iter_eqns(val.jaxpr)
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _iter_sub(v)


def check_closed_jaxpr(name: str, closed) -> List[Finding]:
    """Walk one executable's jaxpr for forbidden primitives/dtypes."""
    path = f"<jaxpr:{name}>"
    messages: List[str] = []
    seen = set()

    def emit(msg: str) -> None:
        if msg not in seen:
            seen.add(msg)
            messages.append(msg)

    for eqn in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        if any(s in pname for s in _CALLBACK_SUBSTR):
            emit(f"host callback primitive '{pname}' staged into the "
                 "executable — every step would round-trip the host")
        if pname == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            if new in _WIDE_DTYPES:
                emit(f"convert_element_type to {new} — f64 promotion in "
                     "the hot path (check jax_enable_x64 leaks and numpy "
                     "scalar mixing)")
        if pname == "device_put":
            devs = eqn.params.get("devices", ()) or ()
            srcs = eqn.params.get("srcs", ()) or ()
            blob = f"{devs}{srcs}".lower()
            if "host" in blob or "pinned" in blob:
                emit(f"device_put with host memory kind ({pname}) — "
                     "transfer staged into the executable")
        for v in getattr(eqn, "outvars", ()):
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _WIDE_DTYPES:
                emit(f"{dt} intermediate produced by '{pname}'")
    return [Finding(RULE_ID, path, 0, m) for m in messages]


def run_jaxpr_pass() -> List[Finding]:
    """Trace every registered executable and collect findings."""
    _ensure_defaults()
    out: List[Finding] = []
    for name, builder in sorted(_REGISTRY.items()):
        try:
            closed = builder()
        except Exception as exc:            # noqa: BLE001 - report, not die
            out.append(Finding(
                RULE_ID, f"<jaxpr:{name}>", 0,
                f"hot-path executable failed to trace: {exc!r}"))
            continue
        out.extend(check_closed_jaxpr(name, closed))
    return out


# ---------------------------------------------------------------------------
# Default registry: the serve path's jitted executables over TINY
# ---------------------------------------------------------------------------
_DEFAULTS_DONE = False


def _ensure_defaults() -> None:
    global _DEFAULTS_DONE
    if _DEFAULTS_DONE:
        return
    _DEFAULTS_DONE = True
    _register_defaults()


@functools.lru_cache(maxsize=1)
def _abstract_serve_state():
    """Abstract (shape-only) params/caches/logits for a TINY decode batch."""
    import jax
    import jax.numpy as jnp

    from repro.configs.scope_estimator import TINY
    from repro.models import model as M
    from repro.serving import sampler

    cfg = TINY
    B, L, T = 2, 8, 4
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(functools.partial(M.init_params, cfg=cfg), key)
    tokens = jax.ShapeDtypeStruct((B, L), jnp.int32)
    logits, caches = jax.eval_shape(
        lambda p, t: M.prefill(p, cfg, {"tokens": t}), params, tokens)
    padded = jax.eval_shape(
        lambda c: sampler._pad_caches(c, L + T, L), caches)
    last = jax.ShapeDtypeStruct((B, cfg.vocab_size), jnp.float32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    done = jax.ShapeDtypeStruct((B,), jnp.bool_)
    return {"cfg": cfg, "B": B, "L": L, "T": T, "key": key,
            "params": params, "tokens": tokens, "padded": padded,
            "last": last, "pos": pos, "done": done}


def _register_defaults() -> None:
    try:
        import jax
        import jax.numpy as jnp
    except Exception:                       # pragma: no cover - no jax
        return

    from repro.serving import sampler

    @register("fused_decode")
    def _fused_decode():
        s = _abstract_serve_state()
        cfg, T = s["cfg"], s["T"]
        fn = lambda p, lg, c, k, pos, dn: sampler._scan_decode(
            p, cfg, lg, c, k, T, 0.0, True, pos, dn)
        return jax.make_jaxpr(fn)(s["params"], s["last"], s["padded"],
                                  s["key"], s["pos"], s["done"])

    def _paged_state(kernel):
        from repro.serving.kv_pool import PagedSpec, _ceil_div
        s = _abstract_serve_state()
        cfg, B, L, T = s["cfg"], s["B"], s["L"], s["T"]
        page_size = 4
        kv_cap = L + T
        width = _ceil_div(kv_cap, page_size)
        n_pages_total = B * width + 1       # + trash page
        npg = _ceil_div(L, page_size)
        ids = jax.ShapeDtypeStruct((B * npg,), jnp.int32)
        _, pcaches = jax.eval_shape(
            lambda p, t, i, n: sampler._paged_prefill(
                p, cfg, t, n_pages_total, page_size, i, n),
            s["params"], s["tokens"], ids, s["pos"])
        spec = PagedSpec(page_size=page_size, kv_cap=kv_cap, kernel=kernel)
        table = jax.ShapeDtypeStruct((B, width), jnp.int32)
        return s, pcaches, spec, table, ids

    def _paged_builder(kernel):
        def build():
            s, pcaches, spec, table, _ = _paged_state(kernel)
            cfg, T = s["cfg"], s["T"]
            fn = lambda p, lg, c, k, tbl, pos, dn: \
                sampler._paged_scan_decode(p, cfg, lg, c, k, T, 0.0, True,
                                           spec, tbl, pos, dn)
            return jax.make_jaxpr(fn)(s["params"], s["last"], pcaches,
                                      s["key"], table, s["pos"], s["done"])
        return build

    from repro.kernels.decode_attention import KernelType
    register("paged_segment_scan")(_paged_builder(KernelType.XLA))
    register("paged_segment_scan_pallas")(_paged_builder(KernelType.PALLAS))

    @register("paged_fused_refill")
    def _paged_fused_refill():
        from repro.kernels.decode_attention import KernelType
        s, pcaches, spec, table, ids = _paged_state(KernelType.XLA)
        cfg, B, T = s["cfg"], s["B"], s["T"]
        rows = jax.ShapeDtypeStruct((B,), jnp.int32)
        rlens = jax.ShapeDtypeStruct((B,), jnp.int32)
        fn = lambda p, lg, c, k, tbl, pos, dn, r, rp, rl, ri: \
            sampler._paged_refill_scan_decode(
                p, cfg, lg, c, k, T, 0.0, True, spec, tbl, pos, dn,
                r, rp, rl, ri)
        return jax.make_jaxpr(fn)(s["params"], s["last"], pcaches,
                                  s["key"], table, s["pos"], s["done"],
                                  rows, s["tokens"], rlens, ids)

    @register("tier0_forward")
    def _tier0_forward():
        from repro.models import tier0 as T0
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        cfg = T0.Tier0Config()
        params = jax.eval_shape(
            functools.partial(T0.init_tier0, cfg=cfg), key)
        n, K = T0.PAIR_BUCKETS[0], 5
        qf = jax.ShapeDtypeStruct((n, T0.QUERY_FEATS), jnp.float32)
        af = jax.ShapeDtypeStruct((n, K, T0.ANCHOR_FEATS), jnp.float32)
        mf = jax.ShapeDtypeStruct((n, T0.MODEL_FEATS), jnp.float32)
        mid = jax.ShapeDtypeStruct((n,), jnp.int32)
        return jax.make_jaxpr(T0.tier0_forward)(params, qf, af, mf, mid)
