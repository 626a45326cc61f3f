"""From a profiler trace to the numbers the per-layer metrics read.

`compact` keeps what the reduction needs from the `.xplane.pb` file the
JAX profiler writes: the device operations and program (module) runs of
every TPU, and the harness's own host spans (names starting "bench.").
`summarize` reduces that event list; it is checked on recorded chip
traces (bench/tests/data).  All times in a compact list are nanoseconds on
the profiler's one clock.  Which ops are collectives is read from the
compiled round program's text (`collective_ops`), by HLO opcode.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}
SPAN_PREFIX = "bench."
TOP = 10
NAME_CHARS = 100      # of an op's HLO text, in the breakdown

COLLECTIVES = frozenset({
    "all-reduce", "all-reduce-start", "all-reduce-done", "all-gather",
    "all-gather-start", "all-gather-done", "reduce-scatter",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "all-to-all", "collective-broadcast"})
# ops that run a computation of their own, which may hold a collective
WRAPPERS = frozenset({"fusion", "async-start", "async-update", "async-done"})
_HLO_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?[\]})] ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"\bcalls=\{?(%?[\w.\-]+(?:,\s*%?[\w.\-]+)*)")


def instruction(event_name: str) -> str:
    """The instruction an op event names: the first token of the event's
    name (`%fusion.72 = (f32[...]) fusion(...)` -> `fusion.72`)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def collective_ops(hlo_text: str) -> frozenset[str]:
    """The instructions of an HLO module's text that exchange data between
    devices: those whose opcode is a collective (all-reduce, all-gather,
    reduce-scatter, collective-permute, all-to-all, their async start and
    done halves), and fusions and async wrappers whose computation holds
    one."""
    comps: dict[str, list] = {}
    body: list = []
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "\t") and line.rstrip().endswith("{"):
            body = comps.setdefault(
                line.removeprefix("ENTRY ").split()[0].lstrip("%"), [])
            continue
        m = _HLO_OP.match(line)
        if m:
            c = _CALLS.search(line)
            calls = ([x.strip().lstrip("%") for x in c.group(1).split(",")]
                     if c else [])
            body.append((m.group(1), m.group(2), calls))
    memo: dict[str, bool] = {}

    def exchanges(op: str, calls: list) -> bool:
        return op in COLLECTIVES or (op in WRAPPERS and any(
            holds(c) for c in calls))

    def holds(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(exchanges(op, calls)
                             for _, op, calls in comps.get(comp, ()))
        return memo[comp]

    return frozenset(name for insts in comps.values()
                     for name, op, calls in insts if exchanges(op, calls))


def compact(profile_dir: str) -> dict:
    """The compact event list of the newest trace under `profile_dir`:
    {"names": [...], "host": [[name, start, dur]], "devices": {id:
    {"ops": [[name, start, dur]], "modules": [...]}}}, names as indices."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {profile_dir}")
    names: list[str] = []
    ids: dict[str, int] = {}

    def ev(e):
        if e.name not in ids:
            ids[e.name] = len(names)
            names.append(e.name)
        return [ids[e.name], int(e.start_ns), int(e.duration_ns)]

    out = {"names": names, "host": [], "devices": {}}
    for plane in ProfileData.from_file(files[-1]).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name in LINES:
                    dev[LINES[line.name]] += [ev(e) for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [ev(e) for e in line.events
                                if e.name.startswith(SPAN_PREFIX)]
    return out


# --------------------------------------------------------------------------
# Interval arithmetic
# --------------------------------------------------------------------------

def _merge(iv: list) -> list:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv: list, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def _length(iv: list) -> int:
    return sum(e - s for s, e in iv)


def _covered(merged: list, lo: int, hi: int) -> int:
    """Length of [lo, hi) covered by sorted disjoint `merged`."""
    i = max(bisect.bisect_right(merged, [lo]) - 1, 0)
    total = 0
    while i < len(merged) and merged[i][0] < hi:
        total += max(0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return total


def _nesting(ops: list) -> tuple[list, list]:
    """One line's events, which nest (a while or conditional op spans the
    ops of its body), sorted by start, and the time of the events directly
    inside each."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    inner = [0] * len(evs)
    stack: list[int] = []
    for i, (_, s, du) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and s + du <= evs[stack[-1]][1] + evs[stack[-1]][2]:
            inner[stack[-1]] += du
        stack.append(i)
    return evs, inner


def _self_times(ops: list) -> dict[int, int]:
    """{name: total self time} of one line's events: each event's duration
    less that of the events directly inside it."""
    out: dict[int, int] = {}
    for (n, _, du), c in zip(*_nesting(ops)):
        out[n] = out.get(n, 0) + du - c
    return out


def _leaves(ops: list) -> list:
    """The events of one line that hold no other event: the work, not the
    loops and conditionals around it."""
    return [e for e, c in zip(*_nesting(ops)) if not c]


def _in_runs(ops: list, runs: list) -> list:
    """The events that start inside one of the sorted [start, end) runs."""
    starts = [s for s, _ in runs]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < runs[i][1]:
            out.append(e)
    return out


def _collective_times(events: dict, lo: int, hi: int, round_name: int,
                      collectives: frozenset) -> dict:
    """{device: (collective ns, exposed ns) per run of the round program}:
    the union of the device's collective ops inside its runs of the round
    program in [lo, hi), and the part of it in which no other op (of the
    work, not a loop around it) runs on that device."""
    names = events["names"]
    out = {}
    for d, dev in events["devices"].items():
        runs = sorted([s, s + du] for n, s, du in dev["modules"]
                      if n == round_name and s >= lo and s + du <= hi)
        if not runs:
            continue
        inside = _in_runs(dev["ops"], runs)
        is_coll = [instruction(names[e[0]]) in collectives for e in inside]
        coll = _merge([[s, s + du] for (_, s, du), c in zip(inside, is_coll)
                       if c])
        if not coll:
            continue
        work = _merge([[s, s + du] for _, s, du in _leaves(
            [e for e, c in zip(inside, is_coll) if not c])])
        exposed = sum(e - s - _covered(work, s, e) for s, e in coll)
        out[d] = (_length(coll) / len(runs), exposed / len(runs))
    return out


# --------------------------------------------------------------------------
# The summary
# --------------------------------------------------------------------------

def summarize(events: dict, collectives: frozenset = frozenset()) -> dict:
    """Reduce a compact event list to what the metrics read (seconds):
    window_s, busy_s per device, rounds, round_busy_s, round_gap_idle_s,
    the breakdown's device_ops and idle_gaps, and, over the instructions
    `collectives` names, collective_s and collective_exposed_s per run of
    the round program, each on the device where it is largest (None where
    no collective ran), with collective_device and exposed_device."""
    names = events["names"]
    spans = [(names[n], s, s + d) for n, s, d in events["host"]]
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0]
    devs = events["devices"]
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    ops = {d: _clip([[s, s + du] for _, s, du in v["ops"]], lo, hi)
           for d, v in devs.items()}
    merged = {d: _merge(iv) for d, iv in ops.items()}
    first = sorted(devs, key=int)[0]
    dev0 = devs[first]

    # the round program: the module that ran longest in the window
    totals: dict[int, int] = {}
    for n, s, du in dev0["modules"]:
        if s < hi and s + du > lo:
            totals[n] = totals.get(n, 0) + du
    if not totals:
        raise ValueError("no program ran on the device in the window")
    round_name = max(totals, key=totals.get)
    runs = sorted([s, s + du] for n, s, du in dev0["modules"]
                  if n == round_name and s >= lo and s + du <= hi)
    m0 = merged[first]
    round_busy = [_covered(m0, s, e) for s, e in runs]
    gaps = [(s1 - e0) - _covered(m0, e0, s1)
            for (_, e0), (s1, _) in zip(runs, runs[1:])]

    by_op = _self_times([e for e in dev0["ops"]
                         if e[1] < hi and e[1] + e[2] > lo])
    idle = [(b0[1], b1[0]) for b0, b1 in zip(m0, m0[1:])]
    if m0:
        idle = [(lo, m0[0][0])] + idle + [(m0[-1][1], hi)]
    idle = sorted((g for g in idle if g[1] > g[0]),
                  key=lambda g: g[1] - g[0], reverse=True)[:TOP]

    def doing(t):
        inner = [(e - s, n) for n, s, e in spans if s <= t < e]
        return min(inner)[1][len(SPAN_PREFIX):] if inner else "outside"

    coll = _collective_times(events, lo, hi, round_name, collectives)
    c_dev = max(coll, key=lambda d: coll[d][0], default=None)
    x_dev = max(coll, key=lambda d: coll[d][1], default=None)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": {d: _length(m) / 1e9 for d, m in merged.items()},
        "rounds": len(runs),
        "round_program": names[round_name],
        "round_busy_s": (sum(round_busy) / len(runs) / 1e9
                         if runs else None),
        "round_gap_idle_s": sum(gaps) / len(gaps) / 1e9 if gaps else None,
        "device_ops": [[names[n][:NAME_CHARS], t / 1e9] for n, t in sorted(
            by_op.items(), key=lambda x: x[1], reverse=True)[:TOP]],
        "idle_gaps": [[doing((s + e) / 2), (e - s) / 1e9] for s, e in idle],
        "collective_s": coll[c_dev][0] / 1e9 if coll else None,
        "collective_device": c_dev,
        "collective_exposed_s": coll[x_dev][1] / 1e9 if coll else None,
        "exposed_device": x_dev,
    }


def cut(events: dict, rounds: int) -> dict:
    """The first `rounds` round-program runs of a compact list, with the
    window span narrowed to them: a small recording for the tests."""
    names = events["names"]
    lo, hi = next((s, s + d) for n, s, d in events["host"]
                  if names[n] == "bench.window")
    first = sorted(events["devices"], key=int)[0]
    summ = summarize(events)
    runs = sorted(s + d for n, s, d in events["devices"][first]["modules"]
                  if names[n] == summ["round_program"] and lo <= s)
    hi = runs[min(rounds, len(runs)) - 1] + 1
    keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]
    host = [[n, s, (hi - s) if names[n] == "bench.window" else d]
            for n, s, d in keep(events["host"])]
    devices = {k: {"ops": keep(v["ops"]), "modules": keep(v["modules"])}
               for k, v in events["devices"].items()}
    used = sorted({e[0] for e in host} | {e[0] for v in devices.values()
                                         for evs in v.values() for e in evs})
    remap = {old: i for i, old in enumerate(used)}
    re_ev = lambda evs: [[remap[n], s - lo, d] for n, s, d in evs]
    return {"names": [names[i][:NAME_CHARS] for i in used],
            "host": re_ev(host),
            "devices": {k: {kk: re_ev(vv) for kk, vv in v.items()}
                        for k, v in devices.items()}}
