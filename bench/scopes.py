"""The round program's device time by part, and the device's idle time
under the engine's host spans.

The program names the parts of its round with `jax.named_scope`: `grad`
(the local step's forward and backward), `optimizer`, `sync` and
`telemetry`.  XLA keeps the scope path in each instruction's `op_name`
metadata, and a fusion carries the path of its root op.  `op_parts` keys
every instruction of the compiled round's text (`compiled.as_text()`) to
its part; `split` reduces a compact event list (bench/trace.py) with that
map to device self time per part and round.  The engine puts host spans
named `repro.engine.*` around its own work (`RoundEngine.run_round`);
`engine_spans` adds them to a compact list, and `engine_idle` reduces them
to the device-idle time under each name per round.  Times in seconds.
"""
from __future__ import annotations

import glob
import os
import re

from bench import trace

SCOPES = ("grad", "optimizer", "sync", "telemetry")
PARTS = ("forward", "backward", "optimizer", "sync", "telemetry", "other")
ENGINE_PREFIX = "repro."

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"')
# a path component naming a scope, bare or inside transformations:
# `sync`, `vmap(grad)`, `transpose(jvp(grad))`
_SCOPE = re.compile(r"^(?:[\w\-]+\()*(%s)\)*$" % "|".join(SCOPES))


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


def op_parts(hlo_text: str) -> dict[str, str]:
    """{instruction name: part} of every instruction in an HLO module's
    text that carries an `op_name`; an instruction without one is other."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = part_of(m.group(2))
    return out


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


def split(events: dict, parts: dict[str, str]) -> dict[str, float]:
    """{part: device self time per run of the round program}, each of
    PARTS, for the ops of the runs in the window; an op `parts` does not
    name is other."""
    ops, rounds = round_ops(events)
    out = dict.fromkeys(PARTS, 0.0)
    for op, t in ops.items():
        out[parts.get(op, "other")] += t / rounds
    return out


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
