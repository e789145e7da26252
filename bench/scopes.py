"""Where a served round's time goes, read from the names the serving program
writes on the profiler's clock.

- Host spans from ``Scheduler.step``: ``serve.step`` (one a round, a
  ``StepTraceAnnotation``) holding ``serve.admin``, ``serve.assemble``,
  ``serve.dispatch``, ``serve.wait`` (the one blocking read of the round's
  outputs) and ``serve.deliver``.
- Scopes in the step program's HLO ``op_name``: ``chunk_lane``,
  ``decode_lane``, ``draft``, ``verify``, ``sample``, ``guard``, and in
  every layer ``kv_write`` and ``attend``.
- Pallas kernels by name (``lutmul_onehot_fused``, ``lutmul_tmac_fused``,
  ``int_matmul_fused``, ...): a named kernel's ``op_name`` ends
  ``<name>/pallas_call``.

A TPU op event carries only its device times, no ``op_name``.  The
profiler keeps each program's compiled HLO on the ``/host:metadata`` plane
(one ``Hlo Proto`` stat per program), so ``load`` finds an op's program by
the ``XLA Modules`` event around it and its ``op_name`` by instruction
name.  Instructions the compiler added (whole-cache copies,
rematerialized slices) have no ``op_name`` of their own and take that of
the loop holding them.

    python -m bench.scopes <trace dir>

prints a JSON summary: host time by phase a round, device time by scope
and by kernel name, the busy time no name claims, and the idle gaps, each
labelled by the innermost host span under it.  ``bench/run.py`` loads
every traced run with ``load``, so a metric reader finds the spans and
each op's scope in ``ctx.trace`` and reads them with ``share``,
``busy_each`` and ``phase_ms``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
import sys
from pathlib import Path

from bench import trace

PROGRAM_SPANS = "serve."                 # prefix of the scheduler's spans
PHASES = ("serve.admin", "serve.assemble", "serve.dispatch", "serve.wait",
          "serve.deliver")
HOST_PHASES = tuple(p for p in PHASES if p != "serve.wait")
PROGRAM_SCOPES = ("chunk_lane", "decode_lane", "draft", "verify", "sample",
                  "guard", "kv_write", "attend")
MODULES_LINE = "XLA Modules"             # one event per program run
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_NAME = re.compile(r"^%?([\w.-]+) = ")   # any instruction, tuples too
_RESULT = re.compile(r"^%?[\w.-]+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
_KERNEL = re.compile(r"[A-Za-z_]\w*")


@dataclasses.dataclass
class ScopedTrace(trace.Trace):
    """``bench.trace.Trace`` with the program's spans among ``spans`` and,
    per device plane, one (scope path, kernel name or "") per op."""
    scopes: dict = dataclasses.field(default_factory=dict)

    @property
    def steps(self) -> list:
        return [(a, b) for n, a, b in self.spans if n == "serve.step"]


def load(trace_dir) -> ScopedTrace:
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    raw = Path(files[0]).read_bytes()
    names = program_op_names(raw)
    ops, spans, scopes = {}, [], {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines
                     if ln.name in (trace.OPS_LINE, MODULES_LINE)}
            evs = [e for e in lines.get(trace.OPS_LINE, [])
                   if not trace.CONTAINER.match(e.name)]
            ops[plane.name] = [(e.name, e.start_ns,
                                e.start_ns + e.duration_ns) for e in evs]
            scopes[plane.name] = join_scopes(
                [(e.name, e.start_ns) for e in evs],
                sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in lines.get(MODULES_LINE, [])), names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name in trace.HOST_SPANS
                             or e.name.startswith(PROGRAM_SPANS))
    spans.sort(key=lambda s: s[1])
    return ScopedTrace(ops, spans, scopes)


def join_scopes(evs: list, modules: list, names: dict) -> list:
    """(scope path, kernel name) of each (op name, start) in ``evs``: its
    program is the ``modules`` event (start, end, name) around its start;
    ``names`` maps a program to its instructions' op_names."""
    starts = [m[0] for m in modules]
    seen: dict = {}
    out = []
    for name, t in evs:
        i = bisect.bisect_right(starts, t) - 1
        prog = modules[i][2] if i >= 0 and t < modules[i][1] else ""
        m = _NAME.match(name)
        key = (prog, m.group(1) if m else "")
        if key not in seen:
            seen[key] = op_scope(names.get(prog, {}).get(key[1], ""))
        out.append(seen[key])
    return out


def op_scope(path: str) -> tuple:
    """(scope path, kernel name) of an op from its ``op_name``.  An unnamed
    kernel's path ends in a JAX call wrapper (``jit(f)``,
    ``closed_call``), which names no kernel."""
    parts = path.split("/")
    name = parts[-2] if len(parts) > 2 and parts[-1] == "pallas_call" \
        else ""
    kernel = name if _KERNEL.fullmatch(name) and name != "closed_call" \
        else ""
    return path, kernel


def under(scope: tuple, name: str) -> bool:
    """The op lies under the named scope (a whole component of its path)."""
    return name in scope[0].split("/")


def scoped(scope: tuple) -> bool:
    """A kernel name or one of the step's scopes claims the op."""
    return bool(scope[1]) or any(p in PROGRAM_SCOPES
                                 for p in scope[0].split("/"))


# -- the compiled HLO the profiler keeps ------------------------------------

def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def program_op_names(raw: bytes) -> dict:
    """{program name as its ``XLA Modules`` events give it ("jit_run(<id>)")
    -> {instruction -> op_name}} from the ``Hlo Proto`` stats of a
    serialized XSpace's metadata plane (XSpace: planes 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5, both maps of key 1 to value 2;
    XEventMetadata: name 2, stats 5; XStatMetadata: id 1, name 2; XStat:
    metadata_id 1, bytes_value 6; HloProto: hlo_module 1)."""
    out = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and bytes(v) == METADATA_PLANE.encode()
                   for k, v in fields):
            continue
        stat_ids = set()
        for k, v in fields:
            if k == 5:
                stat = dict(_fields(dict(_fields(v))[2]))
                if bytes(stat.get(2, b"")) == HLO_PROTO_STAT.encode():
                    stat_ids.add(stat.get(1, 0))
        for k, v in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v))[2]))
            name = next((bytes(x).decode() for j, x in meta if j == 2), "")
            for j, x in meta:
                stat = dict(_fields(x)) if j == 5 else {}
                if stat.get(1) in stat_ids and 6 in stat:
                    out[name] = hlo_op_names(dict(_fields(stat[6]))[1])
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                    r"false_computation)=%?([\w.-]+)|"
                    r"\bbranch_computations=\{([^}]*)\}")


def hlo_op_names(module_proto) -> dict:
    """{instruction -> op_name} of a serialized HloModuleProto.  An
    instruction without an op_name of its own takes that of the instruction
    whose computation holds it: a copy in a while loop's body belongs to
    the loop's scope."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions()
    opts.print_metadata = True
    opts.print_backend_config = False
    text = _jax.HloModule.from_serialized_hlo_module_proto(
        bytes(module_proto)).to_string(opts)
    own, home, caller = {}, {}, {}
    comp = ""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        inst, rest = m.groups()
        home[inst] = comp
        op = _OP_NAME.search(rest)
        if op and op.group(1):
            own[inst] = op.group(1)
        for one, branches in _CALLS.findall(rest):
            for callee in [one] if one else branches.split(", "):
                caller.setdefault(callee.lstrip("%"), inst)

    def resolve(inst: str, depth: int = 0) -> str:
        if inst in own or depth > 64:
            return own.get(inst, "")
        up = caller.get(home.get(inst, ""))
        return resolve(up, depth + 1) if up else ""
    return {inst: resolve(inst) for inst in home}


# -- time by name -----------------------------------------------------------

def busy_each(tr: ScopedTrace, keep, spans: list) -> list:
    """Busy nanoseconds in each [lo, hi) of ``spans`` of the ops for which
    ``keep(name, scope)`` holds, averaged over the device planes.  A plane's
    ops come in order of their start, so each span looks only at the ops
    that can reach it, and ``keep`` runs once per distinct (name, scope)."""
    tot = [0.0] * len(spans)
    seen: dict = {}
    for plane, ev in tr.ops.items():
        sc = tr.scopes.get(plane) or [("", "")] * len(ev)
        starts = [a for _, a, _ in ev]
        longest = max((b - a for _, a, b in ev), default=0)
        for k, (lo, hi) in enumerate(spans):
            picked = []
            for i in range(bisect.bisect_left(starts, lo - longest),
                           bisect.bisect_left(starts, hi)):
                key = (ev[i][0], sc[i])
                if key not in seen:
                    seen[key] = keep(*key)
                if seen[key]:
                    picked.append(ev[i])
            tot[k] += trace.busy_ns(picked, lo, hi)
    n = max(len(tr.ops), 1)
    return [t / n for t in tot]


def share(tr: ScopedTrace, spans: list, keep):
    """% of the spans' device busy time in ops for which ``keep`` holds."""
    whole = sum(busy_each(tr, lambda n, s: True, spans))
    return 100.0 * sum(busy_each(tr, keep, spans)) / whole if whole \
        else None


def cache_op(shapes: set):
    """``keep`` for the KV cache's own traffic: ops under ``kv_write``, and
    the copies, slices and update-slices whose result has one of
    ``shapes`` (a whole K or V leaf [L, slots, max_len, Hkv, D] or one
    layer of it), which the layer scan and the compiler add outside it."""
    def keep(name: str, scope: tuple) -> bool:
        m = _RESULT.match(name)
        return under(scope, "kv_write") or bool(
            m and tuple(int(x) for x in m.group(1).split(",") if x) in shapes
            and re.search(r"copy|dynamic-update-slice|dynamic-slice",
                          _NAME.match(name).group(1) + " " + m.group(2)))
    return keep


def phase_ms(tr: ScopedTrace, rounds: list) -> list:
    """{phase: ms} of each [lo, hi] of ``rounds``: the summed length of the
    ``serve.*`` phase spans inside it."""
    return [{p: sum(b - a for n, a, b in tr.spans
                    if n == p and a >= lo and b <= hi) / 1e6
             for p in PHASES} for lo, hi in rounds]


def innermost(spans: list, t: float) -> str:
    """The name of the innermost host span holding ``t``: of the spans
    around it, the one that starts last (then the shortest)."""
    around = [(a, a - b, n) for n, a, b in spans if a <= t < b]
    return max(around)[2] if around else "no host span"


def idle_gaps(tr: ScopedTrace, lo: float, hi: float) -> list:
    """(innermost host span, seconds) of every gap in [lo, hi) in which no
    op ran on a device, longest first."""
    gaps = []
    for ev in tr.ops.values():
        merged = trace.union(((a, b) for _, a, b in ev), lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(innermost(tr.spans, (a + b) / 2), (b - a) / 1e9)
                 for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return sorted(gaps, key=lambda g: -g[1])


def summary(tr: ScopedTrace, top: int = 10) -> dict:
    """Rounds are the benchmark's ``bench.round`` spans where the trace has
    them, else the program's ``serve.step`` spans."""
    rounds = tr.rounds or tr.steps
    if not rounds:
        return {"rounds": 0}
    lo, hi = rounds[0][0], rounds[-1][1]
    per = phase_ms(tr, rounds)
    busy = sum(busy_each(tr, lambda n, s: True, rounds))
    by_scope: dict = {}
    for plane, ev in tr.ops.items():
        for (_, a, b), (path, kernel) in zip(ev, tr.scopes.get(plane, [])):
            if a >= lo and b <= hi:
                key = "/".join(p for p in path.split("/")
                               if p in PROGRAM_SCOPES) or "-"
                by_scope[key] = by_scope.get(key, 0) + b - a
    n = max(len(tr.ops), 1)
    unscoped = sum(busy_each(tr, lambda n, s: not scoped(s), rounds))
    named = sum(busy_each(tr, lambda n, s: bool(s[1]), rounds))
    return {
        "rounds": len(rounds),
        "phase_ms": {p: sum(r[p] for r in per) / len(per) for p in PHASES},
        "host_ms": [sum(r[p] for p in HOST_PHASES) for r in per],
        "phase_cover": min(sum(r.values()) * 1e6 / (b - a)
                           for r, (a, b) in zip(per, rounds)),
        "busy_s": busy / 1e9,
        "scope_s": dict(sorted(((k, v / n / 1e9)
                                for k, v in by_scope.items()),
                               key=lambda kv: -kv[1])),
        "kernel_share": 100.0 * named / busy if busy else None,
        "unscoped_share": 100.0 * unscoped / busy if busy else None,
        "idle_gaps": [[k, v] for k, v in idle_gaps(tr, lo, hi)[:top]],
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python -m bench.scopes <trace dir>", file=sys.stderr)
        return 2
    print(json.dumps(summary(load(args[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
