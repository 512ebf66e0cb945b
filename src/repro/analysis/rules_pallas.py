"""Rule: pallas-kernel-contract.

Statically-checkable half of the Pallas kernel contract the paged decode
path relies on (``kernels/decode_attention.py``):

- every ``pl.pallas_call`` declares a ``grid`` or ``grid_spec`` (an
  implicit single-program grid hides indexing bugs);
- each ``BlockSpec`` index-map lambda takes exactly ``len(grid)`` program
  indices — plus ``num_scalar_prefetch`` leading refs under a
  ``PrefetchScalarGridSpec`` (the page table / lengths the paged kernel
  prefetches);
- index maps are pure address arithmetic: no calls inside the lambda;
- no rank-1 block shapes: per-row scalars (lengths, page tables) ride
  in SMEM through ``PrefetchScalarGridSpec`` — the TPU lowering refuses
  a sub-tile rank-1 block whatever its ``memory_space``;
- ``interpret=True`` is never hardcoded (pass it through so TPU runs
  compile; see ``kernels/backend.resolve_interpret``).

Grid/block divisibility and index-map *bounds* against ``PagedSpec``
depend on runtime shapes, so they are enforced by layer 2: the jaxpr pass
traces the registered paged executables, and pallas validates block
shapes against array shapes at trace time — a violation fails the trace
and surfaces as a finding there.
"""
from __future__ import annotations

import ast
from typing import Iterator, List, Optional

from repro.analysis.astpass import ModuleContext, Rule, dotted
from repro.analysis.findings import Finding

_PALLAS_CALL = frozenset({"pl.pallas_call", "pallas_call",
                          "pltpu.pallas_call"})
_GRID_SPECS = frozenset({"pltpu.PrefetchScalarGridSpec",
                         "PrefetchScalarGridSpec", "pl.GridSpec",
                         "GridSpec"})
_BLOCK_SPECS = frozenset({"pl.BlockSpec", "BlockSpec"})


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _tuple_len(node: Optional[ast.AST]) -> Optional[int]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return len(node.elts)
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return 1
    return None


def _block_specs(node: Optional[ast.AST]) -> List[ast.Call]:
    """BlockSpec constructor calls in an in_specs/out_specs expression."""
    if node is None:
        return []
    out: List[ast.Call] = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and dotted(sub.func) in _BLOCK_SPECS:
            out.append(sub)
    return out


class PallasContractRule(Rule):
    id = "pallas-kernel-contract"
    description = ("pallas_call grid/BlockSpec contract: index-map arity, "
                   "pure index maps, no rank-1 blocks (scalar prefetch "
                   "instead), no hardcoded interpret mode")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    dotted(node.func) in _PALLAS_CALL:
                yield from self._check_call(ctx, node)

    def _check_call(self, ctx: ModuleContext,
                    call: ast.Call) -> Iterator[Finding]:
        grid = _kw(call, "grid")
        grid_spec = _kw(call, "grid_spec")
        if grid is None and grid_spec is None:
            yield ctx.finding(
                self.id, call,
                "pallas_call without grid= or grid_spec= — declare the "
                "program grid explicitly")
            return
        n_prefetch = 0
        specs_holder = call
        if grid_spec is not None and isinstance(grid_spec, ast.Call) and \
                dotted(grid_spec.func) in _GRID_SPECS:
            grid = _kw(grid_spec, "grid") or grid
            pf = _kw(grid_spec, "num_scalar_prefetch")
            if isinstance(pf, ast.Constant) and isinstance(pf.value, int):
                n_prefetch = pf.value
            specs_holder = grid_spec
        ndims = _tuple_len(grid)

        interp = _kw(call, "interpret")
        if isinstance(interp, ast.Constant) and interp.value is True:
            yield ctx.finding(
                self.id, interp,
                "interpret=True hardcoded — thread it through (backend "
                "probe) so the kernel compiles on TPU")

        for spec in (_block_specs(_kw(specs_holder, "in_specs")) +
                     _block_specs(_kw(specs_holder, "out_specs"))):
            yield from self._check_block_spec(ctx, spec, ndims, n_prefetch)

    def _check_block_spec(self, ctx: ModuleContext, spec: ast.Call,
                          ndims: Optional[int],
                          n_prefetch: int) -> Iterator[Finding]:
        shape = spec.args[0] if spec.args else _kw(spec, "block_shape")
        index_map = spec.args[1] if len(spec.args) > 1 \
            else _kw(spec, "index_map")
        if isinstance(index_map, ast.Lambda):
            arity = len(index_map.args.args)
            if ndims is not None and arity != ndims + n_prefetch:
                want = f"{ndims} grid indices" + (
                    f" + {n_prefetch} scalar-prefetch refs"
                    if n_prefetch else "")
                yield ctx.finding(
                    self.id, index_map,
                    f"index map takes {arity} args but the grid implies "
                    f"{want} — each program axis must be addressed")
            for sub in ast.walk(index_map.body):
                if isinstance(sub, ast.Call):
                    yield ctx.finding(
                        self.id, sub,
                        "call inside a BlockSpec index map — index maps "
                        "must be pure address arithmetic")
                    break
        if _tuple_len(shape) == 1:
            yield ctx.finding(
                self.id, spec,
                "rank-1 BlockSpec — per-row scalars belong in SMEM via "
                "PrefetchScalarGridSpec(num_scalar_prefetch=...); the TPU "
                "lowering refuses sub-tile rank-1 blocks")

    triggers = (
        """\
import jax
from jax.experimental import pallas as pl

def bad(x, kernel):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True,
    )(x)

def bad2(x, kernel, table):
    return pl.pallas_call(
        kernel,
        grid=(4, 4),
        in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, lookup(j))),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)
""",
    )
    non_triggers = (
        """\
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def good(x, lens, kernel, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 2, 8),
        in_specs=[
            pl.BlockSpec((1, 1, 8, 16), lambda b, h, i, n: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 16), lambda b, h, i, n: (b, h, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(lens, x)
""",
    )
