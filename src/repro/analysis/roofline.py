"""Roofline terms from the compiled dry-run artifact (assignment §Roofline).

The three terms are *derived* from the compiled module (which a CPU host
can produce for a described TPU v5e), not measured on a chip:

    compute term    = HLO_FLOPs / (chips * peak FLOP/s)
    memory term     = HLO_bytes / (chips * HBM bandwidth)
    collective term = collective bytes / (chips * ICI link bandwidth)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes;
``compiled.as_text()`` (the post-SPMD, per-device module) for collective
operand bytes — all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  Shapes in the partitioned module are PER-DEVICE, so
cost_analysis flops/bytes and the collective tally are per-chip; dividing
the global quantity by ``chips`` (the assignment formula) is equivalent to
using the per-chip numbers directly, which is what we do.

MODEL_FLOPS uses the 6·N·D rule (6·N_active·D for MoE; 2·N·D forward-only
for prefill/decode) so the "useful compute" ratio catches remat/redundancy
waste.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

import jax
import numpy as np

# ---- TPU v5e hardware constants (assignment) -----------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
FP32_PENALTY = 4.0           # fp32 dots run at ~1/4 the bf16 MXU rate

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _line_collective(stripped: str) -> tuple[str, int] | None:
    """(kind, bytes) for one HLO instruction line, else None.

    Sums the OPERAND shapes when the printer inlines them; otherwise falls
    back to the result shape(s) (which lie inside the match span,
    "= f32[..] all-reduce(")."""
    m = re.search(r"=\s*[a-z0-9]+\[[0-9,]*\][^=]*?\s("
                  + "|".join(_COLLECTIVES) + r")[\.\(]", stripped)
    if not m:
        # tuple-result collectives: "= (f32[..], f32[..]) all-reduce("
        m = re.search(r"=\s*\(.*\)\s(" + "|".join(_COLLECTIVES)
                      + r")[\.\(]", stripped)
        if not m:
            return None
    kind = m.group(1)
    operand_shapes = _SHAPE_RE.findall(stripped[m.end():])
    if operand_shapes:
        b = sum(_shape_bytes(d, s) for d, s in operand_shapes)
    else:
        res = _SHAPE_RE.findall(stripped[m.start():m.end()])
        b = sum(_shape_bytes(d, s) for d, s in res)
    return kind, b


_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_TRIP_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> tuple[dict, str | None]:
    """name -> list of instruction lines; also returns the ENTRY name."""
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        if not line:
            continue
        if not line.startswith(" "):
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
                continue
            cur = None
        elif cur is not None:
            comps[cur].append(line.strip())
    return comps, entry


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind operand bytes for ONE EXECUTION of a
    (per-device) HLO module.

    Collectives inside ``while`` bodies (lax.scan over layers, chunked CE,
    q-chunk scans) execute trip-count times but are printed once, so the
    tally walks the call graph: bytes(comp) = own + called comps +
    trip_count x while-body comps. Trip counts are read from the loop
    condition's comparison constant (a conservative max over its integer
    constants)."""
    comps, entry = _split_computations(hlo_text)
    if entry is None:                      # fall back: flat line scan
        out = {k: 0 for k in _COLLECTIVES}
        for line in hlo_text.splitlines():
            r = _line_collective(line.strip())
            if r:
                out[r[0]] += r[1]
        return out

    memo: dict[str, dict[str, float]] = {}

    def trip_count(cond_name: str) -> int:
        consts = [int(x) for x in _TRIP_RE.findall(
            "\n".join(comps.get(cond_name, [])))]
        return max(consts) if consts else 1

    def resolve(name: str, stack: tuple = ()) -> dict[str, float]:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return {k: 0.0 for k in _COLLECTIVES}
        total = {k: 0.0 for k in _COLLECTIVES}
        for line in comps[name]:
            r = _line_collective(line)
            if r:
                total[r[0]] += r[1]
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                n = trip_count(cond)
                sub = resolve(body, stack + (name,))
                for k in total:
                    total[k] += n * sub[k]
                continue
            for callee in _CALL_RE.findall(line):
                sub = resolve(callee, stack + (name,))
                for k in total:
                    total[k] += sub[k]
        memo[name] = total
        return total

    out = resolve(entry)
    return {k: int(v) for k, v in out.items()}


# --------------------------------------------------------------------------
# dtype-aware dot accounting (fp32 dots pay a ~4x MXU penalty on v5e)
# --------------------------------------------------------------------------

_INSTR_RE = re.compile(r"^%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]")
_DOT_RE = re.compile(r"\b(dot|convolution)\(([^)]*)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def fp32_dot_flops(hlo_text: str) -> tuple[float, float]:
    """(fp32_dot_flops, total_dot_flops) for ONE execution of a per-device
    module — trip-count-aware like collective_bytes.

    A dot's flops = 2 * prod(result dims) * prod(lhs contracting dims);
    it is charged the fp32 penalty when its LHS operand is f32/f64 (the
    MXU runs bf16; fp32 matmuls decompose into multiple passes)."""
    comps, entry = _split_computations(hlo_text)
    if entry is None:
        comps, entry = {"__all__": hlo_text.splitlines()}, "__all__"

    # per-computation symbol tables: name -> (dtype, dims)
    tables: dict[str, dict[str, tuple[str, list[int]]]] = {}
    for cname, lines in comps.items():
        t = {}
        for line in lines:
            m = _INSTR_RE.match(line.strip())
            if m:
                dims = [int(x) for x in m.group(3).split(",") if x]
                t[m.group(1)] = (m.group(2), dims)
        tables[cname] = t

    memo: dict[str, tuple[float, float]] = {}

    def line_dot(cname: str, line: str) -> tuple[float, float]:
        m = _DOT_RE.search(line)
        if not m or "= " not in line:
            return 0.0, 0.0
        hdr = _INSTR_RE.match(line.strip())
        if not hdr:
            return 0.0, 0.0
        out_dims = [int(x) for x in hdr.group(3).split(",") if x]
        out_n = 1
        for d in out_dims:
            out_n *= d
        ops = _OPERAND_RE.findall(m.group(2))
        lhs = tables[cname].get(ops[0]) if ops else None
        k = 1
        cm = _CONTRACT_RE.search(line)
        if lhs and cm:
            for ci in (int(x) for x in cm.group(1).split(",") if x):
                if ci < len(lhs[1]):
                    k *= lhs[1][ci]
        flops = 2.0 * out_n * k
        is_fp32 = bool(lhs) and lhs[0] in ("f32", "f64")
        return (flops if is_fp32 else 0.0), flops

    def trip_count(cond_name: str) -> int:
        consts = [int(x) for x in _TRIP_RE.findall(
            "\n".join(comps.get(cond_name, [])))]
        return max(consts) if consts else 1

    def resolve(name: str, stack: tuple = ()) -> tuple[float, float]:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return 0.0, 0.0
        f32, tot = 0.0, 0.0
        for line in comps[name]:
            a, b = line_dot(name, line)
            f32 += a
            tot += b
            wm = _WHILE_RE.search(line)
            if wm:
                n = trip_count(wm.group(1))
                sa, sb = resolve(wm.group(2), stack + (name,))
                f32 += n * sa
                tot += n * sb
                continue
            for callee in _CALL_RE.findall(line):
                sa, sb = resolve(callee, stack + (name,))
                f32 += sa
                tot += sb
        memo[name] = (f32, tot)
        return memo[name]

    return resolve(entry)


def param_counts(cfg) -> tuple[int, int]:
    """(total params, active params). Active discounts routed experts by
    top_k/E (MoE); equal for dense archs."""
    from repro.models import transformer as T
    shapes = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    total = 0
    active = 0.0
    routed = {"w_gate", "w_up", "w_down"}

    def visit(path, leaf):
        nonlocal total, active
        names = [str(getattr(k, "key", "")) for k in path]
        n = int(np.prod(leaf.shape))
        total += n
        if cfg.is_moe and "moe" in names and names[-1] in routed:
            active += n * cfg.num_experts_per_tok / cfg.num_experts
        else:
            active += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    return total, int(active)


def model_flops(cfg, shape) -> float:
    """6·N·D (train), 2·N·D (forward-only prefill / decode); N = active."""
    _, n_active = param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: ONE token per sequence
    return 2.0 * n_active * shape.global_batch


@dataclass
class RooflineTerms:
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    coll_breakdown: dict


def roofline_from_lowered(lowered, compiled, cfg, shape, mesh) -> dict:
    """The §Roofline record for one (arch, shape, mesh) combination."""
    chips = mesh.size
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    coll_total = float(sum(coll.values()))

    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll_total / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    useful = mf / (flops * chips) if flops > 0 else float("nan")
    return {
        "chips": chips,
        "flops_per_chip": flops,
        "bytes_per_chip": byts,
        "coll_bytes_per_chip": coll_total,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops": mf,
        "useful_ratio": useful,
        "coll_breakdown": {k: v for k, v in coll.items() if v},
    }


def bound_step_time(rec: dict) -> float:
    """Lower-bound step time: max of the three terms (no overlap model)."""
    return max(rec["compute_s"], rec["memory_s"], rec["collective_s"])


# --------------------------------------------------------------------------
# depth-extrapolated roofline (the accurate path)
# --------------------------------------------------------------------------
#
# cost_analysis() visits a `while` body ONCE, so the layer-stacked scan that
# keeps the official dry-run HLO compact makes FLOPs/bytes under-report by
# ~num_layers x. For the roofline we therefore lower REDUCED-depth variants
# with structural scans fully unrolled (models.scan_config) at two depths
# L1 < L2, fit cost(L) = a + b*L exactly, and extrapolate to the real
# depth. Dims, batch, sequence and mesh are the real ones — only the layer
# count is reduced, so the per-layer HLO (and its collectives) is the real
# per-layer program.

def _analysis_depths(cfg) -> tuple[int, int]:
    if cfg.shared_attn_period:                 # zamba: whole groups
        return cfg.shared_attn_period, 2 * cfg.shared_attn_period
    fd = cfg.first_dense_layers
    return fd + 2, fd + 4


def _measure(cfg, shape, mesh, *, fsdp: bool | None, remat: bool) -> dict:
    import dataclasses

    from repro.launch.specs import lower_step
    from repro.models import scan_config

    with scan_config.unrolled():
        lowered = lower_step(cfg, shape, mesh, fsdp=fsdp, remat=remat)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    text = compiled.as_text()
    coll = collective_bytes(text)
    f32_dots, _ = fp32_dot_flops(text)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "f32_dots": f32_dots,
            "coll": coll}


def roofline_extrapolated(cfg, shape, mesh, *, fsdp: bool | None = None,
                          remat: bool = True) -> dict:
    """§Roofline record via two reduced-depth unrolled lowerings."""
    import dataclasses

    l1, l2 = _analysis_depths(cfg)
    l_full = cfg.num_layers
    m1 = _measure(dataclasses.replace(cfg, num_layers=l1), shape, mesh,
                  fsdp=fsdp, remat=remat)
    m2 = _measure(dataclasses.replace(cfg, num_layers=l2), shape, mesh,
                  fsdp=fsdp, remat=remat)

    def extrap(v1: float, v2: float) -> float:
        b = (v2 - v1) / (l2 - l1)
        a = v1 - b * l1
        return max(a + b * l_full, v2)       # clamp: cost grows with depth

    flops = extrap(m1["flops"], m2["flops"])
    byts = extrap(m1["bytes"], m2["bytes"])
    f32_dots = extrap(m1["f32_dots"], m2["f32_dots"])
    coll = {k: extrap(m1["coll"].get(k, 0), m2["coll"].get(k, 0))
            for k in set(m1["coll"]) | set(m2["coll"])}
    coll_total = float(sum(coll.values()))

    # dtype-aware compute term: fp32 dots pay the MXU penalty
    compute_s = (flops + f32_dots * (FP32_PENALTY - 1.0)) / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll_total / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    chips = mesh.size
    return {
        "chips": chips,
        "method": f"unrolled-extrapolated(L={l1},{l2}->{l_full})",
        "f32_dot_flops_per_chip": f32_dots,
        "f32_dot_share": f32_dots / flops if flops else 0.0,
        "flops_per_chip": flops,
        "bytes_per_chip": byts,
        "coll_bytes_per_chip": coll_total,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops": mf,
        "useful_ratio": mf / (flops * chips) if flops else float("nan"),
        "coll_breakdown": {k: int(v) for k, v in coll.items() if v},
    }
