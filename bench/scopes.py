"""The round program's device time by part and by scope, and the device's
idle time under the engine's host spans.

The program names the parts of its round with `jax.named_scope`: `grad`
(the local step's forward and backward), `optimizer`, `sync` and
`telemetry`.  XLA keeps the scope path in each instruction's `op_name`
metadata, and a fusion carries the path of its root op.  `op_names` keys
every instruction of the compiled round's text (`compiled.as_text()`) to
its `op_name`; `summarize` reduces a compact event list (bench/trace.py)
with that map to device self time per round by part (`part_of`, the
innermost of the four scopes) and by every scope on the ops' name stacks
(`scope_names`), so that a scope the program adds is read with no change
here.  The engine puts host spans named `repro.engine.*` around its own
work (`RoundEngine.run_round`); `engine_spans` adds them to a compact
list, and `engine_idle` reduces them to the device-idle time under each
name per round.  Times in seconds.
"""
from __future__ import annotations

import glob
import os
import re

from bench import trace

# the scopes that make the parts; the parts' meaning is fixed, so a scope
# added inside one of these (a `moe` inside `grad`) leaves the split as it is
SCOPES = ("grad", "optimizer", "sync", "telemetry")
PARTS = ("forward", "backward", "optimizer", "sync", "telemetry", "other")
ENGINE_PREFIX = "repro."

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"')
# a path component naming a scope, bare or inside transformations:
# `sync`, `vmap(grad)`, `transpose(jvp(grad))`
_SCOPE = re.compile(r"^(?:[\w\-]+\()*(%s)\)*$" % "|".join(SCOPES))
# any name stack component, bare or inside transformations
_NAME = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


def part_of(op_name: str) -> str:
    """The part of the round an op belongs to: the innermost scope in its
    `op_name` path; inside `grad`, backward where the path holds a
    `transpose(` (the remat recompute among it), else forward.  XLA joins
    the paths of ops it merged with ';': the first that names a scope
    decides."""
    for path in op_name.split(";"):
        inner = None
        for component in path.split("/"):
            m = _SCOPE.match(component)
            if m:
                inner = m.group(1)
        if inner == "grad":
            return "backward" if "transpose(" in path else "forward"
        if inner:
            return inner
    return "other"


def scope_names(op_name: str) -> set[str]:
    """The names on an op's name stack: each component of each of its
    `op_name` paths but the last, which names the primitive, taken out of
    the transformations around it (`vmap(jvp(moe))` -> `moe`).  These are
    the program's `jax.named_scope`s and the names JAX puts there (a jitted
    function's, `while`, `checkpoint`, an einsum's subscripts)."""
    out = set()
    for path in op_name.split(";"):
        for component in path.split("/")[:-1]:
            m = _NAME.match(component)
            if m and m.group(1):
                out.add(m.group(1))
    return out


def op_names(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} of every instruction in an HLO module's
    text that carries an `op_name`."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def op_parts(hlo_text: str) -> dict[str, str]:
    """{instruction name: part} of every instruction in an HLO module's
    text that carries an `op_name`; an instruction without one is other."""
    return {op: part_of(n) for op, n in op_names(hlo_text).items()}


def sync_collectives(hlo_text: str) -> frozenset[str]:
    """The collectives of an HLO module's text (trace.collective_ops) that
    are not in the `telemetry` scope: the sync's exchanges, those that lost
    their `op_name` in lowering (the sync's reduce-scatter among them)
    included, and not the divergence telemetry's all-reduce of the
    parameters."""
    parts = op_parts(hlo_text)
    return frozenset(op for op in trace.collective_ops(hlo_text)
                     if parts.get(op) != "telemetry")


instruction = trace.instruction


def engine_spans(events: dict, profile_dir: str) -> dict:
    """`events` (trace.compact of `profile_dir`) with the engine's host
    spans, those whose names start with "repro.", added to its host list
    under their full names."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    names = list(events["names"])
    ids = {n: i for i, n in enumerate(names)}
    host = list(events["host"])
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ENGINE_PREFIX):
                    if e.name not in ids:
                        ids[e.name] = len(names)
                        names.append(e.name)
                    host.append([ids[e.name], int(e.start_ns),
                                 int(e.duration_ns)])
    return {**events, "names": names, "host": host}


def _window_and_runs(events: dict):
    """The window's bounds, the first device's id, and the [start, end)
    runs of the round program inside the window (as trace.summarize)."""
    names = events["names"]
    lo, hi = next((s, s + d) for n, s, d in events["host"]
                  if names[n] == "bench.window")
    first = sorted(events["devices"], key=int)[0]
    round_name = trace.summarize(events)["round_program"]
    runs = sorted([s, s + d] for n, s, d in
                  events["devices"][first]["modules"]
                  if names[n] == round_name and s >= lo and s + d <= hi)
    return lo, hi, first, runs


def round_ops(events: dict) -> tuple[dict[str, float], int]:
    """({instruction: device self time}, rounds) over the runs of the
    round program in the window, on its first device."""
    names = events["names"]
    _, _, first, runs = _window_and_runs(events)
    inside = trace._in_runs(events["devices"][first]["ops"], runs)
    out: dict[str, float] = {}
    for n, t in trace._self_times(inside).items():
        op = instruction(names[n])
        out[op] = out.get(op, 0.0) + t / 1e9
    return out, len(runs)


def summarize(events: dict, names: dict[str, str]) -> dict:
    """The round's device self time per run of the round program, over its
    runs in the window on its first device, with `names` ({instruction:
    op_name}, `op_names`): "parts", {part: time} of each of PARTS, and
    "scopes", {name: time of the ops with that name on their stack} of
    every name on the round's stacks (`scope_names`), each of SCOPES
    included (0.0 where no op has it).  An op `names` does not name is in
    other and in no scope."""
    ops, rounds = round_ops(events)
    parts = dict.fromkeys(PARTS, 0.0)
    inclusive = dict.fromkeys(SCOPES, 0.0)
    for op, t in ops.items():
        name = names.get(op, "")
        parts[part_of(name)] += t / rounds
        for s in scope_names(name):
            inclusive[s] = inclusive.get(s, 0.0) + t / rounds
    return {"parts": parts, "scopes": inclusive}


def engine_idle(events: dict) -> dict[str, float]:
    """{span name: device-idle time under that engine span per run of the
    round program}, over the engine's spans in the window; a span's time
    includes that of the spans inside it."""
    names = events["names"]
    lo, hi, first, runs = _window_and_runs(events)
    busy = trace._merge([[s, s + d] for _, s, d in
                         events["devices"][first]["ops"]])
    out: dict[str, float] = {}
    for n, s, d in events["host"]:
        name = names[n]
        if name.startswith(ENGINE_PREFIX) and s >= lo and s + d <= hi:
            idle = d - trace._covered(busy, s, s + d)
            out[name] = out.get(name, 0.0) + idle / 1e9 / len(runs)
    return out
