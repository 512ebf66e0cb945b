"""Post-SPMD HLO analysis with while-loop (scan) trip-count scaling.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
empirically), so a 94-layer scanned model under-reports FLOPs ~94x.  This
module re-derives roofline terms from ``compiled.as_text()``:

  * matmul FLOPs from every ``dot`` (output elems x contraction size x 2),
  * HBM traffic from the I/O of post-fusion ops (fusion boundaries ~= HBM
    materialization points),
  * collective bytes per op kind,

each scaled by the enclosing scans' trip counts, which are recovered from
the loop-condition computations' integer constants.  All numbers are
PER-DEVICE (post-partitioning shapes).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
                "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops whose inputs/outputs approximate HBM traffic in post-fusion HLO
_IO_OPS = set(COLLECTIVES) | {
    "fusion", "dot", "convolution", "copy", "dynamic-slice",
    "dynamic-update-slice", "scatter", "gather", "transpose", "reduce",
    "reduce-window", "concatenate", "slice", "pad", "convert", "broadcast",
    "select-and-scatter", "sort", "reverse", "custom-call",
}


def _shapes_in(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
        out.append((dt, dims))
    return out


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _shapes_in(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


class Instruction:
    __slots__ = ("name", "type_str", "op", "rest")

    def __init__(self, name, type_str, op, rest):
        self.name = name
        self.type_str = type_str
        self.op = op
        self.rest = rest


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^()]*\)|\S+)\s+([\w\-]+)\((.*)$")
_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")


def parse_hlo(text: str):
    comps: Dict[str, List[Instruction]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for line in text.splitlines():
        hm = _HEADER_RE.match(line)
        if hm:
            cur = hm.group(2)
            comps[cur] = []
            if hm.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        im = _INSTR_RE.match(line)
        if im:
            comps[cur].append(Instruction(im.group(1), im.group(2),
                                          im.group(3), im.group(4)))
    return comps, entry


def entry_parameters(text: str) -> Dict[int, Tuple[int, ...]]:
    """Entry-computation parameter number -> its shape."""
    comps, entry = parse_hlo(text)
    out = {}
    for ins in comps[entry]:
        if ins.op == "parameter":
            out[int(re.match(r"\s*(\d+)", ins.rest).group(1))] = tuple(
                _shapes_in(ins.type_str)[0][1])
    return out


def aliased_parameters(text: str) -> List[int]:
    """Entry parameter numbers that the module's ``input_output_alias``
    hands to an output: donated arguments whose buffer the executable
    updates in place and returns."""
    header = text.split("\n", 1)[0]
    return sorted(int(p) for p in re.findall(r"\{[\d,]*\}:\s*\((\d+),",
                                             header))


def ops_shaped_like(text: str, ops, shapes) -> List[str]:
    """``computation/instruction`` of every instruction, fused
    computations included, whose opcode is in ``ops`` and whose result has
    one of ``shapes`` up to the order of its dimensions (so a transposed
    copy counts)."""
    want = {tuple(sorted(s)) for s in shapes}
    comps, _ = parse_hlo(text)
    return [f"{c}/{ins.name}" for c, instrs in comps.items() for ins in instrs
            if ins.op in ops and any(tuple(sorted(d)) in want
                                     for _, d in _shapes_in(ins.type_str))]


def analyze(text: str) -> Dict:
    comps, entry = parse_hlo(text)

    symbols: Dict[str, Dict[str, str]] = {
        c: {i.name: i.type_str for i in instrs}
        for c, instrs in comps.items()
    }

    def fused_param_reads(fname: str) -> Dict[int, int]:
        """Actual read bytes per fusion parameter: a parameter consumed
        only through dynamic-slice/gather reads just the slice."""
        reads: Dict[int, int] = {}
        if fname not in comps:
            return reads
        pnames: Dict[str, int] = {}
        for ins in comps[fname]:
            if ins.op == "parameter":
                m = re.match(r"(\d+)", ins.rest)
                if m:
                    pnames[ins.name] = int(m.group(1))
        for pname, idx in pnames.items():
            full = _type_bytes(symbols[fname].get(pname, ""))
            slice_b = None
            sliced_only = True
            for ins in comps[fname]:
                if ins.op == "parameter":
                    continue
                if re.search(r"%" + re.escape(pname) + r"\b", ins.rest):
                    if ins.op in ("dynamic-slice", "gather"):
                        b = _type_bytes(ins.type_str)
                        slice_b = b if slice_b is None else max(slice_b, b)
                    else:
                        sliced_only = False
            if sliced_only and slice_b is not None:
                reads[idx] = slice_b
            else:
                reads[idx] = full
        return reads

    def comp_direct(cname: str):
        flops = 0.0
        io_bytes = 0.0
        coll = {c: 0.0 for c in COLLECTIVES}
        coll_n = {c: 0 for c in COLLECTIVES}
        whiles: List[Tuple[str, Optional[str]]] = []
        syms = symbols[cname]
        for ins in comps[cname]:
            if ins.op == "while":
                bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
                cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                if bm:
                    whiles.append((bm.group(1), cm.group(1) if cm else None))
                continue
            if ins.op == "dot":
                out_elems = 1
                shapes = _shapes_in(ins.type_str)
                if shapes:
                    for d in shapes[0][1]:
                        out_elems *= d
                arg = re.search(r"%([\w.\-]+)", ins.rest)
                contract = 1
                cd = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
                if arg and cd and arg.group(1) in syms:
                    lhs_shapes = _shapes_in(syms[arg.group(1)])
                    if lhs_shapes:
                        dims = lhs_shapes[0][1]
                        for ax in (cd.group(1).split(",") if cd.group(1) else []):
                            ia = int(ax)
                            if ia < len(dims):
                                contract *= dims[ia]
                flops += 2.0 * out_elems * contract
            if ins.op in _IO_OPS:
                out_b = _type_bytes(ins.type_str)
                args_part = ins.rest.split("),")[0]
                arg_names = [am.group(1) for am in
                             re.finditer(r"%([\w.\-]+)", args_part)]
                arg_b = [(_type_bytes(syms[a]) if a in syms else 0)
                         for a in arg_names]
                # per-op HBM policy: sliced reads/writes touch only the
                # slice, not the buffer they index into
                if ins.op in ("dynamic-slice", "gather"):
                    b = 2 * out_b
                elif ins.op == "dynamic-update-slice":
                    upd = arg_b[1] if len(arg_b) > 1 else out_b
                    b = 2 * upd
                elif ins.op == "scatter":
                    upd = arg_b[-1] if arg_b else out_b
                    b = 2 * upd
                elif ins.op in ("broadcast", "iota"):
                    b = out_b
                elif ins.op in COLLECTIVES:
                    b = 2 * out_b
                elif ins.op == "fusion":
                    fm = re.search(r"calls=%?([\w.\-]+)", ins.rest)
                    if fm:
                        reads = fused_param_reads(fm.group(1))
                        b = out_b + sum(
                            reads.get(i, ab)
                            for i, ab in enumerate(arg_b))
                    else:
                        b = out_b + sum(arg_b)
                else:
                    b = out_b + sum(arg_b)
                io_bytes += b
                if ins.op in COLLECTIVES:
                    coll[ins.op] += out_b
                    coll_n[ins.op] += 1
        return flops, io_bytes, coll, coll_n, whiles

    direct = {c: comp_direct(c) for c in comps}

    def trip_count(cond: Optional[str]) -> int:
        if cond is None or cond not in comps:
            return 1
        ints = []
        # constants appear in instruction text; scan raw rest strings
        for ins in comps[cond]:
            ints += [int(x) for x in re.findall(r"constant\((\d+)\)",
                                                f"{ins.op}({ins.rest}")]
        # also plain 'constant(N)' lines parse as op 'constant'
        return max(ints) if ints else 1

    memo: Dict[str, Tuple[float, float, Dict[str, float]]] = {}

    def total(cname: str, depth: int = 0):
        if cname in memo:
            return memo[cname]
        if depth > 16 or cname not in direct:
            return 0.0, 0.0, {c: 0.0 for c in COLLECTIVES}
        fl, io, coll, _, whiles = direct[cname]
        fl_t, io_t, coll_t = fl, io, dict(coll)
        for body, cond in whiles:
            t = trip_count(cond)
            bf, bio, bcoll = total(body, depth + 1)
            fl_t += bf * t
            io_t += bio * t
            for c in COLLECTIVES:
                coll_t[c] += bcoll[c] * t
        memo[cname] = (fl_t, io_t, coll_t)
        return memo[cname]

    if entry is None:
        entry = max(comps, key=lambda c: len(comps[c]))
    fl, io, coll = total(entry)
    counts = {c: sum(direct[b][3][c] for b in direct) for c in COLLECTIVES}
    return {
        "flops": fl,
        "hbm_bytes": io,
        "collective_bytes": coll,
        "collective_total_bytes": float(sum(coll.values())),
        "collective_op_counts": counts,
        "entry": entry,
    }
