"""Extract roofline inputs from a compiled (AOT) executable.

 - FLOPs / bytes-accessed from compiled.cost_analysis()
 - per-device memory from compiled.memory_analysis()
 - collective bytes parsed from the optimized HLO text: operand sizes of
   all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
 - collective *counts* per kind (the latency axis): proves layout claims
   like "flat sync = one all-reduce per dtype bucket, not per leaf".
"""
from __future__ import annotations

import re

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_RG_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_RG_LIST = re.compile(r"replica_groups=\{\{([0-9,{} ]*)\}\}")


def _crosses_pod(line: str, pod_size: int) -> bool | None:
    """True if any replica group spans devices from different pods
    (device id // pod_size differs).  None if no group info found."""
    m = _RG_IOTA.search(line)
    if m:
        g, n = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.reshape(dims).transpose(perm).reshape(-1)
        groups = ids.reshape(g, n)
        pods = groups // pod_size
        return bool((pods != pods[:, :1]).any())
    m = _RG_LIST.search(line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in grp.replace("{", "").replace("}", "").split(",") if x.strip()]
            if len({i // pod_size for i in ids}) > 1:
                return True
        return False
    return None


def _dtype_bytes_map(shapes) -> dict[str, int]:
    out: dict[str, int] = {}
    for dt, dims in shapes:
        out[dt] = out.get(dt, 0) + _shape_bytes(dt, dims)
    return out


def _iter_collectives(hlo_text: str):
    """Yield one dict per collective op in the optimized HLO, with
    start/done pairs reported once (on the -start line):

      {kind, line, bytes_full, bytes_result, dtype, dtypes}

    bytes_result sums the *result* type(s) only — for reduce-scatter that
    is the per-device owned chunk (the scatter leg); bytes_full takes the
    larger of (result, operands) — the full-tensor roofline size for
    gather/scatter ops.  `dtypes` maps element type -> bytes over the
    larger side, covering every operand of a variadic op: the wire payload
    classifier — how tests prove the ring sync keeps int8 on every
    collective-permute hop and that no f32 tensor rides a quantized wire.
    `dtype` (the first result element type) is kept for compatibility but
    blind to mixed-dtype tuples; classify with `dtypes`."""
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w\.\-]+\s*=\s*(.*)$", s)
        if m is None:
            continue
        rest = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rest):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done(" in rest:
            continue  # avoid double counting start/done pairs
        shapes = _SHAPE_RE.findall(rest)
        if not shapes:
            continue
        # result type(s) appear before the op name; operands may not carry
        # inline types in optimized HLO.
        head, _, tail = rest.partition(kind)
        rshapes = _SHAPE_RE.findall(head) or shapes
        oshapes = _SHAPE_RE.findall(tail)
        if (f"{kind}-start(" in rest and kind in ("all-gather", "reduce-scatter")
                and len(rshapes) >= 2 and len(rshapes) % 2 == 0):
            # async gather/scatter results are (operand..., result...) tuples;
            # keep only the result half so the operand copy isn't counted as
            # a second payload.
            half = len(rshapes) // 2
            if not oshapes or rshapes[:half] == oshapes:
                rshapes = rshapes[half:]
        nb = lambda sh: sum(_shape_bytes(dt, dims) for dt, dims in sh)
        res = nb(rshapes)
        full_shapes = rshapes if res >= nb(oshapes) else oshapes
        yield {
            "kind": kind,
            "line": line,
            "bytes_full": max(res, nb(oshapes)),
            "bytes_result": res,
            "dtype": rshapes[0][0],
            "dtypes": _dtype_bytes_map(full_shapes),
        }


def collective_bytes(hlo_text: str, pod_size: int = 0) -> dict[str, int]:
    """Sum full-tensor sizes of collective ops in the optimized HLO, per
    kind.

    For all-reduce / all-to-all / collective-permute, result size == operand
    size.  For all-gather the result is the gathered (full) tensor and for
    reduce-scatter the operand is the full tensor; in both cases the bytes
    that actually cross links per device are ~the full-tensor size x
    (n-1)/n, so the full-tensor size is the right roofline input.  We report
    the larger of (result, operands) per op.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["dci"] = 0  # pod-crossing bytes (multi-pod meshes only)
    for op in _iter_collectives(hlo_text):
        out[op["kind"]] += op["bytes_full"]
        if pod_size and _crosses_pod(op["line"], pod_size):
            out["dci"] += op["bytes_full"]
    return out


def collective_result_bytes(hlo_text: str) -> dict[str, int]:
    """Sum *result* sizes per kind — the per-device landing size of each
    leg.  This is where the sharded sync's scatter-leg win shows: a
    reduce-scatter's result is the owned 1/W chunk, ~W x smaller than the
    all-reduce result the flat layout pays per bucket; the matching
    all_gather (result: the full bucket) is the leg `--sync overlap` hides
    behind the next round's first local steps."""
    out = {k: 0 for k in _COLLECTIVES}
    for op in _iter_collectives(hlo_text):
        out[op["kind"]] += op["bytes_result"]
    return out


def collective_ops(hlo_text: str) -> list[dict]:
    """Per-op collective detail:
    [{kind, bytes_full, bytes_result, dtype, dtypes}] in HLO order.  This is
    the view that separates a *scale* collective from a *payload*
    collective: the quantized sharded sync's amax fold is one all-reduce of
    4 bytes per model tensor (`payload_profile` classifies any all-reduce
    at most that size as the fold; a bucket-sized all-reduce would be a
    lowering regression).  `dtypes` maps element type -> bytes across every
    operand of a variadic op — the ring sync's acceptance proof filters
    payload-sized ops and asserts every one is s8
    (`payload_profile` `payload_bytes_by_dtype`)."""
    return [{k: op[k] for k in
             ("kind", "bytes_full", "bytes_result", "dtype", "dtypes")}
            for op in _iter_collectives(hlo_text)]


def collective_counts(hlo_text: str) -> dict[str, int]:
    """Number of collective *ops* per kind (start/done pairs count once).

    This is the latency/launch-overhead axis the byte totals miss: a sync
    that moves the same bytes in one all-reduce per dtype bucket
    (--param-layout flat) instead of one per pytree leaf issues O(#dtypes)
    collectives instead of O(#leaves) — and the flat_sharded layout's sync
    must show exactly one reduce-scatter + one all-gather per bucket (the
    acceptance measures; see core/flat.py, tests/test_flat.py and
    tests/test_sharded.py).
    """
    out = {k: 0 for k in _COLLECTIVES}
    for op in _iter_collectives(hlo_text):
        out[op["kind"]] += 1
    return out


def fold_limit(n_leaves: int) -> int:
    """Max byte size of a *scale* collective: the quantized sync's amax
    fold is f32 per model tensor, all buckets concatenated — 4 bytes per
    leaf plus alignment slack.  Anything bigger is wire payload."""
    return 4 * n_leaves + 64


def payload_profile(hlo_text: str, *, n_leaves: int) -> dict:
    """Classify every collective in a sync program as *scale* (the amax
    fold and the ring's scalar per-hop scales — at most `fold_limit`
    bytes) or *payload* (bucket-sized: the bytes QSR actually saves), and
    report the wire picture the layout acceptance claims are written
    against.  Extracted from launch/sync_compare so the declarative rule
    registry (repro.analysis.rules), the audit CLI and the lowering tests
    all read the same record."""
    counts = collective_counts(hlo_text)
    nbytes = collective_bytes(hlo_text)
    legs = collective_result_bytes(hlo_text)
    limit = fold_limit(n_leaves)
    ops = collective_ops(hlo_text)
    ars = [op for op in ops if op["kind"] == "all-reduce"]
    fold = [op for op in ars if op["bytes_full"] <= limit]
    payload = [op for op in ops if op["bytes_full"] > limit]
    by_dtype_bytes: dict[str, int] = {}
    by_dtype_ops: dict[str, int] = {}
    for op in payload:
        # per-dtype over every operand of the (possibly variadic) op, so a
        # f32 tensor hiding in a mixed tuple cannot masquerade as the
        # first operand's dtype
        for dt, b in op["dtypes"].items():
            if b > limit:
                by_dtype_bytes[dt] = by_dtype_bytes.get(dt, 0) + b
                by_dtype_ops[dt] = by_dtype_ops.get(dt, 0) + 1
    return {
        "collective_counts": counts,
        "collective_bytes": {k: v for k, v in nbytes.items() if v},
        "collective_leg_bytes": {k: v for k, v in legs.items() if v},
        "all_reduce_ops": counts["all-reduce"],
        "amax_fold_ops": len(fold),
        "amax_fold_bytes": sum(op["bytes_full"] for op in fold),
        "payload_all_reduce_ops": len(ars) - len(fold),
        "reduce_scatter_ops": counts["reduce-scatter"],
        "all_gather_ops": counts["all-gather"],
        "bytes_on_wire": sum(v for k, v in nbytes.items() if k != "dci"),
        "scatter_leg_bytes": legs["reduce-scatter"],
        "rs_wire_bytes": nbytes["reduce-scatter"],
        "ag_wire_bytes": nbytes["all-gather"],
        "collective_permute_ops": counts["collective-permute"],
        "permute_wire_bytes": nbytes["collective-permute"],
        "payload_bytes_by_dtype": by_dtype_bytes,
        "payload_ops_by_dtype": by_dtype_ops,
        "n_leaves": n_leaves,
    }


_ALIAS_PAIR = re.compile(r"\{([0-9, ]*)\}:\s*\((\d+)\s*,\s*\{([0-9, ]*)\}")


def donation_aliases(hlo_text: str) -> list[tuple[tuple, int, tuple]]:
    """Parse the entry computation's `input_output_alias={...}` header into
    [(output_index, param_number, param_index)] pairs — the proof that a
    donated state buffer was actually reused for its output (silent
    donation loss doubles device memory; the donation-aliasing rule)."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    # the header nests braces ({0}: (0, {}, may-alias), ...): scan to the
    # matching close by depth counting, then pull the pairs
    i = start + len("input_output_alias=")
    depth, j = 0, i
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo_text[i + 1:j]
    out = []
    for om, pnum, pidx in _ALIAS_PAIR.findall(body):
        oi = tuple(int(x) for x in om.replace(" ", "").split(",") if x)
        pi = tuple(int(x) for x in pidx.replace(" ", "").split(",") if x)
        out.append((oi, int(pnum), pi))
    return out


def _group_sizes(line: str) -> list[int] | None:
    m = _RG_IOTA.search(line)
    if m:
        g, n = int(m.group(1)), int(m.group(2))
        return [n] * g
    m = _RG_LIST.search(line)
    if m:
        return [len([x for x in grp.replace("{", "").replace("}", "").split(",")
                     if x.strip()])
                for grp in m.group(1).split("},{")]
    return None


def degenerate_collectives(hlo_text: str) -> list[str]:
    """Lines of collective ops whose replica groups are all singletons —
    a collective that moves nothing between devices (a partitioner
    regression: pure launch overhead).  collective-permute is judged by
    its source_target_pairs instead and skipped here."""
    out = []
    for op in _iter_collectives(hlo_text):
        if op["kind"] == "collective-permute":
            continue
        sizes = _group_sizes(op["line"])
        if sizes is not None and all(s <= 1 for s in sizes):
            out.append(op["line"].strip())
    return out


_HOST_CALL = re.compile(
    r"custom_call_target=\"[^\"]*(callback|host)[^\"]*\"|\binfeed\(|\boutfeed\(")


def host_callbacks(hlo_text: str) -> list[str]:
    """Lines that round-trip through the host (python callbacks, infeed /
    outfeed) — forbidden inside round programs: one host hop per round
    serializes the overlap pipeline and breaks multi-process runs."""
    return [ln.strip() for ln in hlo_text.splitlines() if _HOST_CALL.search(ln)]


_PALLAS_CALL = re.compile(r'%([A-Za-z_]+)[\w.]*\s*=[^\n]*'
                          r'custom_call_target="tpu_custom_call"')


def pallas_kernels(hlo_text: str) -> list[str]:
    """Names of the Pallas TPU kernels (`tpu_custom_call`s) in a compiled
    program, one entry per call; each kernel's pallas_call names it."""
    return _PALLAS_CALL.findall(hlo_text)


def summarize(compiled, *, n_devices: int) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    pod_size = 256 if n_devices > 256 else 0
    coll = collective_bytes(hlo, pod_size=pod_size)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "per_device_memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "collective_bytes": coll,
        "collective_result_bytes": collective_result_bytes(hlo),
        "collective_counts": collective_counts(hlo),
        "collective_bytes_total": sum(v for k, v in coll.items()
                                      if k != "dci"),
        "dci_bytes": coll["dci"],
        "n_devices": n_devices,
    }
