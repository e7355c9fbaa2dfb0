"""Attribution of a traced window's device time to the program's named
scopes.

The program names its work with ``jax.named_scope`` (the names are in
``repro.obs.trace``); XLA writes the scope path into each HLO
instruction's ``op_name`` metadata. The profiler stores every live
program's HLO (an ``HloProto``) in the trace itself, as a stat of the
``/host:metadata`` plane, so the scopes are read from the same file as
the times and name the programs that ran, instruction for instruction.

The HLO is read because a device event's own stats carry no ``op_name``
(on a v5e: its offset and duration), and because a fusion's ``op_name``
is that of its root instruction alone: the unmask program's keystreams
fuse into one subtraction whose root lies outside the ``keystream``
scope. An operation therefore falls under a scope when any HLO
instruction it runs carries the scope as a component of its ``op_name``
path (before the last, which names the operation itself): a fusion runs
every instruction of its fused computation, a loop those of its body.
Constants and parameters do no work and are left out.

A program without the scopes (or a trace without HLO) attributes nothing,
and the readers then report nothing.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from bench.lib import trace as tr

#: name of the ``HloProto`` stat the profiler writes per program
HLO_STAT = "Hlo Proto"
_SUFFIX = re.compile(r"\(-?\d+\)$")
#: instructions that do no work: XLA shares a constant between fusions
#: (the pad's zero lands in the hop's scalar packing), so their op_name
#: would attribute work that is not theirs
_NO_WORK = ("constant", "parameter")


# --- protobuf wire format, as far as XSpace and HloProto need it ---------

def fields(buf) -> list[tuple[int, object]]:
    """(field number, value) of one serialized protobuf message, in order:
    an int for a varint or fixed-width field, a memoryview for a
    length-delimited one."""
    buf = memoryview(buf)
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        out.append((num, val))
    return out


def _varint(buf, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _ints(vals) -> list[int]:
    """A repeated integer field: each value a varint or a packed run."""
    out = []
    for v in vals:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_protos(space: bytes) -> list[memoryview]:
    """Every serialized ``HloProto`` in a serialized XSpace."""
    out = []
    for num, plane in fields(space):
        if num != 1:  # XSpace.planes
            continue
        pf = fields(plane)
        stat_ids = set()
        for n, entry in pf:  # XPlane.stat_metadata: map<int64, XStatMetadata>
            if n != 5:
                continue
            for k, meta in fields(entry):
                meta = dict(fields(meta)) if k == 2 else {}
                if _text(meta.get(2, b"")) == HLO_STAT:  # name
                    stat_ids.add(meta.get(1))  # id
        if not stat_ids:
            continue
        for n, entry in pf:  # XPlane.event_metadata: map<int64, XEventMetadata>
            if n != 4:
                continue
            for k, meta in fields(entry):
                if k != 2:
                    continue
                for f, stat in fields(meta):  # XEventMetadata.stats
                    if f != 5:
                        continue
                    sf = dict(fields(stat))  # XStat
                    if sf.get(1) in stat_ids and 6 in sf:  # bytes_value
                        out.append(sf[6])
    return out


def module_scopes(proto) -> tuple[str, dict[str, frozenset]]:
    """(module name, {instruction name: the ``op_name`` paths it runs}) of
    one serialized ``HloProto``."""
    module = next(v for n, v in fields(proto) if n == 1)
    name, comps = "", []
    for n, v in fields(module):
        if n == 1:
            name = _text(v)
        elif n == 3:
            comps.append(v)
    own: dict[int, set] = {}       # computation id -> its instructions' op_names
    calls: dict[int, set] = {}     # computation id -> computations it calls
    instrs = []                    # (name, op_name, called computation ids)
    for comp in comps:
        cf = fields(comp)
        cid = next((v for n, v in cf if n == 5), None)
        own[cid], calls[cid] = set(), set()
        for n, ins in cf:
            if n != 2:
                continue
            iname, op_name, called, opcode = "", "", [], ""
            for f, v in fields(ins):
                if f == 1:
                    iname = _text(v)
                elif f == 2:
                    opcode = _text(v)
                elif f == 7:  # OpMetadata
                    op_name = next((_text(x) for g, x in fields(v) if g == 2),
                                   "")
                elif f == 38:
                    called.append(v)
            called = _ints(called)
            if opcode in _NO_WORK:
                op_name = ""
            if op_name:
                own[cid].add(op_name)
            calls[cid].update(called)
            instrs.append((iname, op_name, called))

    closed: dict[int, frozenset] = {}

    def reach(cid, seen=()):
        if cid not in closed:
            names = set(own.get(cid, ()))
            for c in calls.get(cid, ()):
                if c not in seen and c != cid:
                    names |= reach(c, seen + (cid,))
            closed[cid] = frozenset(names)
        return closed[cid]

    out = {}
    for iname, op_name, called in instrs:
        names = {op_name} if op_name else set()
        for c in called:
            names |= reach(c)
        out[iname] = frozenset(names)
    return name, out


def scope_map(space: bytes) -> dict[str, dict[str, frozenset]]:
    """{module name: {instruction name: op_name paths}} of every program
    whose HLO the trace holds. Programs of one name are merged."""
    out: dict[str, dict[str, frozenset]] = {}
    for proto in hlo_protos(space):
        name, instrs = module_scopes(proto)
        mod = out.setdefault(name, {})
        for k, v in instrs.items():
            mod[k] = mod.get(k, frozenset()) | v
    return out


def under(op_names, scopes) -> bool:
    """Whether any path in ``op_names`` lies under a scope in ``scopes``:
    has it as a component before the last, which names the operation."""
    return any(part in scopes for p in op_names
               for part in p.split("/")[:-1])


# --- the traced window ----------------------------------------------------

def module_name(event_name: str) -> str:
    """``jit_safe_hop(7)`` -> ``jit_safe_hop``: the HLO module's name."""
    return _SUFFIX.sub("", event_name)


def scope_s(view: tr.TraceView, hlo: dict, module_prefix: str,
            scopes) -> tuple[float, int]:
    """(seconds per chip, runs per chip) of the programs whose module name
    starts with ``module_prefix``: the seconds are those of their
    operations that fall under any of ``scopes`` (a name or a collection
    of names), each operation counted once. An operation belongs to the
    program run whose ``XLA Modules`` event holds its start."""
    scopes = {scopes} if isinstance(scopes, str) else set(scopes)
    total_ns, runs = 0.0, 0
    for ops, mods in zip(view.ops, view.modules):
        mods = sorted(mods, key=lambda e: e.start_ns)
        starts = [m.start_ns for m in mods]
        runs += sum(m.name.startswith(module_prefix) for m in mods)
        cache: dict[tuple[str, str], bool] = {}
        for op in ops:
            j = bisect.bisect_right(starts, op.start_ns) - 1
            if j < 0 or op.start_ns >= mods[j].end_ns:
                continue
            mod = mods[j].name
            if not mod.startswith(module_prefix):
                continue
            key = (mod, op.name)
            if key not in cache:
                names = hlo.get(module_name(mod), {}).get(
                    tr.short_name(op.name), ())
                cache[key] = under(names, scopes)
            if cache[key]:
                total_ns += op.dur_ns
    chips = view.chips
    return total_ns * 1e-9 / chips, runs // chips


def program_scopes(*attrs: str) -> tuple[str, ...] | None:
    """The scope names ``attrs`` (e.g. ``"KEYSTREAM"``) as the program
    defines them in ``repro.obs.trace``; None for a program that has no
    such names."""
    try:
        from repro.obs import trace as names
        return tuple(getattr(names, a) for a in attrs)
    except (ImportError, AttributeError):
        return None


def scoped_s(view: tr.TraceView, module_prefix: str,
             *attrs: str) -> tuple[float, int] | None:
    """``scope_s`` of the program's scopes ``attrs`` in the trace ``view``
    was read from; None where the program has no such scopes or no
    operation of the window falls under them."""
    names = program_scopes(*attrs)
    if names is None:
        return None
    seconds, runs = scope_s(view, hlo_of(view), module_prefix, names)
    return (seconds, runs) if seconds > 0 else None


#: where ``bench/run.py`` writes a traced run's profile (its ``TRACE_DIR``)
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".bench_trace")

#: trace file of each window read so far: finding one loads every
#: candidate (seconds for a 10 s window), and four readers ask
_found: dict[tuple[float, float], str | None] = {}


def trace_file(view: tr.TraceView, trace_dir: str = TRACE_DIR):
    """The ``.xplane.pb`` under ``trace_dir`` whose window is ``view``'s,
    newest first; None if none is."""
    key = (view.lo, view.hi)
    if key in _found:
        return _found[key]
    files = glob.glob(os.path.join(trace_dir, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    _found[key] = None
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        try:
            if tr.window(tr.host_events(tr.load(path))) == key:
                _found[key] = path
                break
        except ValueError:
            continue
    return _found[key]


@functools.cache
def scope_map_of(path: str) -> dict:
    """``scope_map`` of the trace file ``path``, read once."""
    with open(path, "rb") as f:
        return scope_map(f.read())


def hlo_of(view: tr.TraceView) -> dict:
    """``scope_map`` of the trace ``view`` was read from ({} if none)."""
    path = trace_file(view)
    return {} if path is None else scope_map_of(path)


@functools.cache
def profile_start_ns(path: str) -> int | None:
    """Wall-clock ns (as ``time.time_ns()``) of the trace's zero: its
    events' times are offsets from it."""
    from jax.profiler import ProfileData
    for p in ProfileData.from_file(path).planes:
        for k, v in p.stats:
            if k == "profile_start_time":
                return int(v)
    return None


def window_start_s(view: tr.TraceView) -> float | None:
    """Wall-clock seconds (as ``time.time()``) at which ``view``'s window
    began; None without its trace file."""
    path = trace_file(view)
    start = profile_start_ns(path) if path else None
    return None if start is None else (start + view.lo) * 1e-9
