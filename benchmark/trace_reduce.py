"""From a profiler trace (``.xplane.pb``) to device numbers.

The one reduction of the benchmark: which intervals the device was busy
in, how much device time each ``murmura.*`` scope took (by the outermost
events, and by the innermost under their whole chain of labels), which
operations took most, and what the host was doing in the longest idle gaps.
Read with ``jax.profiler.ProfileData`` and nothing else.

What a v5e trace holds (looked at by hand, PR 25): one plane per chip named
``/device:TPU:<i>``; on it the line ``XLA Ops`` carries one event per
executed HLO operation, with its start and duration, and a ``tf_op`` (or
``name``/``long_name``) stat that holds the operation's ``op_name``
metadata, which is where ``jax.named_scope`` puts ``murmura.train`` and the
rest; the line ``XLA Modules`` carries one event per executed program.  An
operation that runs others (a ``while``, a ``call``) is one event with the
others' events inside its interval, two levels deep at most in the round
program (my look, PR 29).  The host's threads are lines of the plane
``/host:CPU``.
"""

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SCOPE_PREFIX = "murmura."


def union_length(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total length covered by half-open integer intervals."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The idle intervals between the first start and the last end."""
    out, end = [], None
    for a, b in sorted(intervals):
        if end is not None and a > end:
            out.append((end, a))
        end = b if end is None else max(end, b)
    return out


_LABEL = re.compile(re.escape(SCOPE_PREFIX) + r"[^/ )\"']*")


def chain_of(texts: Iterable[str]) -> Optional[str]:
    """Every ``murmura.<scope>`` named in an operation's metadata, outermost
    first, joined by ``/``: ``murmura.train/murmura.attention`` for an
    operation under a label inside the training loop's.  A label that a
    transform repeats (``murmura.x/transpose(jvp(murmura.x))/mul``: the
    backward pass of what ran under ``murmura.x``) counts once."""
    for text in texts:
        labels = _LABEL.findall(text)
        if labels:
            return "/".join(
                label for i, label in enumerate(labels)
                if i == 0 or label != labels[i - 1]
            )
    return None


def scope_of(texts: Iterable[str]) -> Optional[str]:
    """The outermost ``murmura.<scope>`` named in an operation's metadata."""
    chain = chain_of(texts)
    return None if chain is None else chain.split("/", 1)[0]


_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"', re.M
)


_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)

ScopeMap = Dict[str, Dict[str, str]]


def scope_map_from_hlo(texts: Iterable[str]) -> ScopeMap:
    """Program name -> HLO operation name -> chain of ``murmura.*`` labels
    (``chain_of``), from the ``op_name`` metadata of compiled programs' text
    (``compiled.as_text()``, every computation of it, a ``while``'s body
    among them): the join for traces whose events carry no metadata of
    their own.  A fusion has the metadata of its root."""
    out: ScopeMap = {}
    for text in texts:
        module = _HLO_MODULE.search(text)
        ops = out.setdefault(module.group(1) if module else "", {})
        for name, op_name in _HLO_LINE.findall(text):
            chain = chain_of([op_name])
            if chain is not None:
                ops.setdefault(name, chain)
    return out


def op_id(event_name: str) -> str:
    """The HLO operation's name in a device event's name.  On a v5e the
    name is the whole instruction (``%fusion.4 = f32[8]{0} fusion(...)``);
    elsewhere it is the operation's name alone."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _chain_from_map(scope_map: ScopeMap, program: Optional[str], op: str):
    """The label chain of operation ``op`` of the program whose trace name is
    ``program`` (the HLO module's name, at times with a suffix); without a
    program, the chain every program that has such an operation agrees on."""
    if program is not None:
        for module, ops in scope_map.items():
            if module and program.startswith(module):
                return ops.get(op)
    found = {ops[op] for ops in scope_map.values() if op in ops}
    return found.pop() if len(found) == 1 else None


@dataclass
class Reduction:
    """What the metrics' readers read.  Seconds throughout."""

    devices: int = 0
    busy_s: float = 0.0  # mean over devices of the union of op intervals
    window_s: float = 0.0  # first op's start to last op's end, widest device
    scope_s: Dict[str, float] = field(default_factory=dict)  # mean over devices
    # Seconds of the innermost events (those that contain no other event)
    # by their whole chain of labels, ``murmura.train/murmura.<inner>``; an
    # event without a label of its own has that of the event around it.
    leaf_s: Dict[str, float] = field(default_factory=dict)  # mean over devices
    leaf_unscoped_s: float = 0.0
    op_s: Dict[str, float] = field(default_factory=dict)  # mean over devices
    program_s: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    unscoped_s: float = 0.0

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]],
        }


def _event_texts(event) -> List[str]:
    texts = [event.name]
    for key, value in event.stats:
        if isinstance(value, str):
            texts.append(value)
    return texts


def _host_activity(space, a_ns: int, b_ns: int) -> str:
    """The host event that covers most of [a, b): what the host was doing
    while the device sat idle."""
    best, best_cover = "host: nothing recorded", 0
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                cover = min(e, b_ns) - max(s, a_ns)
                # The innermost (shortest) event that still covers most.
                if cover > 0.5 * (b_ns - a_ns) and (
                    best_cover == 0 or (e - s) < best_cover
                ):
                    best, best_cover = f"{line.name.split('/')[0]}: {ev.name}", e - s
    return best


def reduce_space(space, scope_map: Optional[ScopeMap] = None) -> Reduction:
    """Reduce a loaded ``ProfileData``.  ``scope_map`` (see
    :func:`scope_map_from_hlo`) is the fall-back join for traces whose
    events carry no metadata."""
    red = Reduction()
    per_device = []
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        ops, programs = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = list(line.events)
            elif line.name == MODULES_LINE:
                programs = list(line.events)
        if not ops:
            continue
        per_device.append((ops, programs))
    red.devices = len(per_device)
    if not per_device:
        return red
    longest_gaps: List[Tuple[int, int]] = []
    for ops, programs in per_device:
        spans = [(int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in ops]
        red.busy_s += union_length(spans) / 1e9 / red.devices
        window = (max(b for _, b in spans) - min(a for a, _ in spans)) / 1e9
        red.window_s = max(red.window_s, window)
        # Scope and operation times count each event's own duration; a
        # nested (child) event lies inside its parent on this line, so
        # only events that no other event contains are summed there, and
        # only those that contain no other in the innermost table.
        order = sorted(range(len(ops)), key=lambda i: (spans[i][0], -spans[i][1]))
        runs = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in programs
        )
        run_at = 0
        open_events: List[list] = []  # [end, chain, seconds, contains another]

        def close(event):
            _, chain, seconds, parent = event
            if parent:
                return
            if chain is None:
                red.leaf_unscoped_s += seconds
            else:
                red.leaf_s[chain] = red.leaf_s.get(chain, 0.0) + seconds

        for i in order:
            a, b = spans[i]
            ev = ops[i]
            seconds = (b - a) / 1e9 / red.devices
            chain = chain_of(_event_texts(ev))
            if chain is None and scope_map:
                while run_at + 1 < len(runs) and runs[run_at][1] <= a:
                    run_at += 1
                inside = runs and runs[run_at][0] <= a < runs[run_at][1]
                chain = _chain_from_map(
                    scope_map, runs[run_at][2] if inside else None, op_id(ev.name)
                )
            while open_events and b > open_events[-1][0]:
                close(open_events.pop())
            if open_events:
                if b == a:
                    continue  # a marker of no length makes no parent
                open_events[-1][3] = True
                open_events.append([b, chain or open_events[-1][1], seconds, False])
                continue
            open_events.append([b, chain, seconds, False])
            scope = scope_of([chain or ""])
            if scope is None:
                red.unscoped_s += seconds
            else:
                red.scope_s[scope] = red.scope_s.get(scope, 0.0) + seconds
            label = f"%{op_id(ev.name)} [{scope or 'no scope'}]"
            red.op_s[label] = red.op_s.get(label, 0.0) + seconds
        while open_events:
            close(open_events.pop())
        for ev in programs:
            red.program_s[ev.name] = (
                red.program_s.get(ev.name, 0.0) + ev.duration_ns / 1e9 / red.devices
            )
        if not longest_gaps:
            longest_gaps = sorted(gaps(spans), key=lambda g: g[0] - g[1])[:10]
    named: Dict[str, float] = {}
    for a, b in longest_gaps:
        what = _host_activity(space, a, b)
        named[what] = named.get(what, 0.0) + (b - a) / 1e9
    red.idle_gaps = sorted(named.items(), key=lambda kv: -kv[1])
    return red


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def reduce_file(path: str, scope_map: Optional[ScopeMap] = None) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_space(ProfileData.from_file(path), scope_map)


def reduce_dir(trace_dir: str, scope_map: Optional[ScopeMap] = None) -> Reduction:
    path = find_xplane(trace_dir)
    if path is None:
        return Reduction()
    return reduce_file(path, scope_map)


def cut_to_text(space, rounds: int = 1, scope_map: Optional[ScopeMap] = None,
                host_min_ns: int = 200_000, nested: bool = False) -> str:
    """A small copy of a trace as an ``XSpace`` text proto: the device
    planes' program line and outermost operations (with ``nested`` the
    operations inside them too), under their short names and with the
    label chain the join gave each as a stat of its own, and the host's
    longer events; from the first device operation to the
    ``rounds + 1``-th run of the first program (a round's first program
    is the same every round).  How the recorded trace under ``testdata/``
    was made; ``ProfileData.from_text_proto`` reads it back."""
    t0 = t1 = None
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            events = sorted(line.events, key=lambda e: e.start_ns)
            if line.name == OPS_LINE and events:
                t0 = int(events[0].start_ns) if t0 is None else min(t0, int(events[0].start_ns))
            if line.name == MODULES_LINE and events and t1 is None:
                again = [e for e in events if e.name == events[0].name]
                t1 = int(again[min(rounds, len(again) - 1)].start_ns)
    quote = lambda text: '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'
    out = []
    for pid, plane in enumerate(space.planes, 1):
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        names: Dict[str, int] = {}
        body = []
        runs = sorted(
            (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for line in plane.lines if line.name == MODULES_LINE for e in line.events
        ) if device else []
        for lid, line in enumerate(plane.lines, 1):
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops_line = device and line.name == OPS_LINE
            events, outer_end = [], -1
            for ev in sorted(line.events, key=lambda e: (e.start_ns, -e.duration_ns)):
                a, d = int(ev.start_ns), int(ev.duration_ns)
                if a < t0 or a + d > t1 or (not device and d < host_min_ns):
                    continue
                stats = ""
                if ops_line:
                    if a + d <= outer_end and not nested:
                        continue  # a child of the event before
                    outer_end = max(outer_end, a + d)
                    chain = chain_of(_event_texts(ev))
                    if chain is None and scope_map:
                        inside = [r[2] for r in runs if r[0] <= a < r[1]]
                        chain = _chain_from_map(
                            scope_map, inside[0] if inside else None, op_id(ev.name)
                        )
                    if chain is not None:
                        stats = f" stats {{ metadata_id: 1 str_value: {quote(chain)} }}"
                mid = names.setdefault(
                    f"%{op_id(ev.name)}" if ops_line else ev.name, len(names) + 1
                )
                events.append(
                    f"    events {{ metadata_id: {mid} offset_ps: {(a - t0) * 1000} "
                    f"duration_ps: {d * 1000}{stats} }}"
                )
            if events:
                body.append(f"  lines {{ id: {lid} name: {quote(line.name)} "
                            f"timestamp_ns: {t0}\n" + "\n".join(events) + "\n  }")
        if not body:
            continue
        out.append(f"planes {{\n  id: {pid}\n  name: {quote(plane.name)}")
        out.extend(body)
        out.extend(f"  event_metadata {{ key: {i} value {{ id: {i} name: {quote(n)} }} }}"
                   for n, i in names.items())
        if device:
            out.append('  stat_metadata { key: 1 value { id: 1 name: "scope" } }')
        out.append("}")
    return "\n".join(out) + "\n"


def dump(path: str, events: int = 6) -> None:
    """Print what a trace holds: planes, lines, and the first events of
    each line with their stats.  For looking at one by hand."""
    from jax.profiler import ProfileData

    space = ProfileData.from_file(path)
    for plane in space.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:events]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in ev.stats}
                print(f"    {ev.name[:80]!r} start {ev.start_ns} "
                      f"dur {ev.duration_ns} {stats}")


if __name__ == "__main__":
    # ``trace_reduce.py <dir>``: print what the trace holds.
    # ``trace_reduce.py <dir> --cut <rounds> <out> [nested]``: write the
    # small copy (``<dir>/scope_map.json``, which a ``--keep-trace`` run
    # leaves, gives the join), with ``nested`` the inner operations too.
    import json
    import sys

    target = sys.argv[1]
    found = find_xplane(target) if os.path.isdir(target) else target
    joined = os.path.join(os.path.dirname(target) if os.path.isfile(target) else target,
                          "scope_map.json")
    scope_map = json.load(open(joined)) if os.path.exists(joined) else None
    if len(sys.argv) > 2 and sys.argv[2] == "--cut":
        from jax.profiler import ProfileData

        text = cut_to_text(ProfileData.from_file(found), int(sys.argv[3]), scope_map,
                           nested=sys.argv[5:] == ["nested"])
        with open(sys.argv[4], "w") as f:
            f.write(text)
        print(f"{sys.argv[4]}: {len(text)} bytes")
    else:
        dump(found)
        print(reduce_file(found, scope_map))
