"""From a profiler trace (`.xplane.pb`) to device busy time, time by layer,
step spans and the breakdown. Checked by tests/test_trace_reduce.py on the
small recorded trace beside it.

What it reads of a trace: the planes named `/device:TPU:<n>`; on each, the
line `XLA Ops` (one event per executed HLO operation, a `while` enclosing
the operations of its body) and the line `XLA Modules` (one event per
executed program); on the host plane, the `bench.<name>` annotations the
harness wrote around its own calls. An operation's time is its SELF time:
its duration less that of the operations nested inside it, so that the
layers' times add up to the busy time. An operation is given to a layer by
`layers.json`, from the source file and line of the Python frame that made
it. The trace's events carry only the HLO instruction's text; its source is
in the HLO module the profiler stores beside them (the `Hlo Proto` stat of
the `/host:metadata` plane, which jax's ProfileData does not expose, so the
few protobuf fields on the way to it are decoded here by hand). An
instruction the compiler made itself has no source and inherits one from
the computation it calls, its operands or the loop it stands in
(`inherit_sources`); what still has none is the `unattributed` time.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "unattributed"


def load_rules() -> List[Dict]:
    with open(os.path.join(HERE, "layers.json")) as f:
        return json.load(f)["rules"]


def layer_of(name: str, source: str, rules: List[Dict]) -> str:
    for rule in rules:
        if any(name.startswith(p) for p in rule["names"]):
            return rule["layer"]
        if source and any(s in source for s in rule["sources"]):
            return rule["layer"]
    return UNATTRIBUTED


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


# ---------------------------------------------- sources of HLO instructions


def _varint(buf, i: int):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: varints as
    ints, length-delimited fields as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, wire, value


def hlo_modules(path: str) -> Dict[str, bytes]:
    """{module name: serialized HloModuleProto} from the trace file: XSpace
    .planes(1) named `/host:metadata` .event_metadata(4) .value(2)
    {name(2), stats(5) .bytes_value(6) = HloProto .hlo_module(1)}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name = next((bytes(v).decode() for f, _, v in _fields(plane)
                     if f == 2), "")
        if name != "/host:metadata":
            continue
        for pf, _, entry in _fields(plane):
            if pf != 4:
                continue
            for ef, _, meta in _fields(entry):
                if ef != 2:
                    continue
                mname, proto = "", None
                for mf, _, mv in _fields(meta):
                    if mf == 2:
                        mname = bytes(mv).decode()
                    elif mf == 5:
                        for sf, sw, sv in _fields(mv):
                            if sf == 6 and sw == 2:
                                proto = next((bytes(v) for f, _, v
                                              in _fields(sv) if f == 1), None)
                if mname and proto:
                    out[mname] = proto
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s+(.*)$")
_META = re.compile(r"(?<![A-Za-z_])metadata=\{([^}]*)\}")
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|"
                     r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_TABLE_ROW = re.compile(r"^(\d+)\s+(.*)$")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _split_call(rhs: str) -> Tuple[str, str]:
    """(opcode, operand text) of an instruction's right-hand side
    `<type> <opcode>(<operands>), <attributes>`; a tuple type is in
    parentheses of its own."""
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    rest = rhs[i:].lstrip()
    op, _, tail = rest.partition("(")
    depth, end = 1, len(tail)
    for j, c in enumerate(tail):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            end = j
            break
    return op, tail[:end]


def parse_hlo(hlo_text: str) -> Dict[str, Dict]:
    """{instruction name: {"source": "file:line" or "", "target": the
    custom call's target or "", "operands": [names], "calls": [computation
    names], "computation": the one it stands in}} from a module's HLO text.
    The source comes through the `source_file`/`source_line` of the
    instruction's metadata or, where the text has a stack-frame index
    instead, through `stack_frame_id`."""
    tables: Dict[str, Dict[int, str]] = {}
    section = None
    instrs: Dict[str, Dict] = {}
    frames: Dict[str, int] = {}
    computation = ""
    for line in hlo_text.splitlines():
        if line in _TABLES:
            section = tables.setdefault(line, {})
            continue
        if section is not None:
            row = _TABLE_ROW.match(line)
            if row:
                section[int(row.group(1))] = row.group(2)
                continue
            section = None
        head = _COMPUTATION.match(line)
        if head and " = " not in line.split("(", 1)[0]:
            computation = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        op, operands = _split_call(rhs)
        target = _TARGET.search(rhs) if op == "custom-call" else None
        instrs[name] = info = {
            "source": "", "target": target.group(1) if target else "",
            "operands": re.findall(r"%([\w.\-]+)", operands),
            "calls": [c.strip().lstrip("%") for found in _CALLED.findall(rhs)
                      for c in found.strip("{}").split(",")],
            "computation": computation}
        meta = _META.search(rhs)
        if not meta:
            continue
        src = re.search(r'source_file="([^"]*)"', meta.group(1))
        if src:
            ln = re.search(r"source_line=(\d+)", meta.group(1))
            info["source"] = f"{src.group(1)}:{ln.group(1) if ln else 0}"
            continue
        frame = re.search(r"stack_frame_id=(\d+)", meta.group(1))
        if frame:
            frames[name] = int(frame.group(1))
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    for name, frame_id in frames.items():
        frame = tables.get("StackFrames", {}).get(frame_id, "")
        loc = re.search(r"file_location_id=(\d+)", frame)
        where = tables.get("FileLocations", {}).get(
            int(loc.group(1)), "") if loc else ""
        fid = re.search(r"file_name_id=(\d+)", where)
        ln = re.search(r"\bline=(\d+)", where)
        if fid and int(fid.group(1)) in files:
            instrs[name]["source"] = (f"{files[int(fid.group(1))]}:"
                                      f"{ln.group(1) if ln else 0}")
    return instrs


def inherit_sources(instrs: Dict[str, Dict]) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (source, custom-call target)}. The compiler's own
    instructions (copies, sorts, the fusions it builds, the scatters of a
    loop) carry no source; such a one takes, in this order, the commonest
    source among the instructions of the computations it calls (a fusion's
    body), the source of the first operand that has or inherits one, the
    source of the instruction that calls the computation it stands in (a
    `while` for its body), and last the source, its own or its body's, of
    the nearest instruction that its value flows into (a broadcast of zeros
    goes through a tuple into the loop that writes into it)."""
    members: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    for name, info in instrs.items():
        members.setdefault(info["computation"], []).append(name)
        for comp in info["calls"]:
            caller.setdefault(comp, name)
        for operand in info["operands"]:
            users.setdefault(operand, []).append(name)
    out: Dict[str, str] = {}

    def called(name: str, depth: int = 0) -> str:
        tally: Dict[str, int] = {}
        for comp in instrs[name]["calls"]:
            for inner in members.get(comp, ()):
                src = instrs[inner]["source"] or (
                    called(inner, depth + 1) if depth < 4 else "")
                if src:
                    tally[src] = tally.get(src, 0) + 1
        return max(tally, key=tally.get) if tally else ""

    def resolve(name: str, seen: frozenset) -> str:
        if name in out:
            return out[name]
        info = instrs.get(name)
        if info is None or name in seen or len(seen) > 64:
            return ""
        seen = seen | {name}
        src = info["source"] or called(name)
        for operand in info["operands"] if not src else ():
            src = resolve(operand, seen)
            if src:
                break
        if not src and info["computation"] in caller:
            src = resolve(caller[info["computation"]], seen)
        if src:
            out[name] = src
        return src

    def downstream(name: str) -> str:
        front, seen = [name], {name}
        for _ in range(4):
            front = [u for n in front for u in users.get(n, ())
                     if u not in seen and not seen.add(u)]
            for user in front:
                src = instrs[user]["source"] or called(user)
                if src:
                    return src
        return ""

    return {name: (resolve(name, frozenset()) or downstream(name),
                   info["target"]) for name, info in instrs.items()}


def module_sources(path: str) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """{module name: {instruction name: (source "file:line", custom-call
    target)}} for a trace file."""
    from jax._src.lib import xla_client

    out = {}
    for name, proto in hlo_modules(path).items():
        module = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            proto)
        out[name] = inherit_sources(parse_hlo(module.to_string()))
    return out


def instruction_name(event_name: str) -> str:
    """`%fusion.5 = f32[..] fusion(...)` -> `fusion.5`."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def read_events(path: str):
    """(device_ops, modules, host_spans): device_ops {device: [(start_ns,
    dur_ns, instruction name (a custom call's led by its target), source
    "file:line")]} of the `XLA Ops` lines,
    modules {device: [(start_ns, dur_ns, name)]}, host_spans [(start_ns,
    dur_ns, name)] of the harness's `bench.*` annotations."""
    import jax

    sources = module_sources(path)
    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.duration_ns, e.name)
                          for e in lines[MODULES_LINE].events
                          ) if MODULES_LINE in lines else []
            modules[plane.name] = mods
            starts = [m[0] for m in mods]
            found = []
            for e in lines[OPS_LINE].events if OPS_LINE in lines else ():
                name = instruction_name(e.name)
                k = bisect.bisect_right(starts, e.start_ns) - 1
                by_instr = sources.get(mods[k][2], {}) if k >= 0 else {}
                source, target = by_instr.get(name, ("", ""))
                if target and not name.startswith(target):
                    # a kernel the compiler named after its call site
                    name = f"{target}:{name}"
                found.append((e.start_ns, e.duration_ns, name, source))
            if found:
                ops[plane.name] = found
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith("bench."))
    return ops, modules, host


def self_times(events: Iterable[Tuple]) -> List[Tuple]:
    """[(start, dur, name, source, self_ns)]: each event's duration less the
    durations of the events nested directly inside it (one line of a trace:
    events nest or follow each other, they do not partly overlap)."""
    out: List[List] = []
    stack: List[int] = []  # indices into out
    for start, dur, name, source in sorted(events,
                                           key=lambda e: (e[0], -e[1])):
        while stack and start >= out[stack[-1]][0] + out[stack[-1]][1]:
            stack.pop()
        if stack:
            out[stack[-1]][4] -= dur
        out.append([start, dur, name, source, dur])
        stack.append(len(out) - 1)
    return [tuple(e[:4]) + (max(e[4], 0.0),) for e in out]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, host_spans, top: int = 10):
    """The longest gaps between device operations, each named by the
    harness's host span that covers most of it."""
    gaps = []
    cur_e = None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            gaps.append((s - cur_e, cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    gaps.sort(reverse=True)
    out = []
    for length, a, b in gaps[:top]:
        best, cover = "no bench span", 0.0
        for hs, hd, name in host_spans:
            c = min(b, hs + hd) - max(a, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, length * 1e-9])
    return out


def reduce_events(ops: Dict[str, List], modules: Dict[str, List],
                  host_spans: List, chips: int,
                  window_s: Optional[float] = None) -> Dict:
    if not ops:
        raise RuntimeError("the trace holds no `XLA Ops` line of a TPU "
                           "device: nothing ran on the device, or the "
                           "profiler's names changed")
    rules = load_rules()
    devices = sorted(ops)[:chips]
    by_layer: Dict[str, float] = {}
    by_file: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    busy = 0.0
    for dev in devices:
        timed = self_times(ops[dev])
        busy += union_ns((s, s + d) for s, d, *_ in timed)
        for start, dur, name, source, self_ns in timed:
            layer = layer_of(name, source, rules)
            by_layer[layer] = by_layer.get(layer, 0.0) + self_ns
            where = source.rsplit(":", 1)[0] if source else "(no source)"
            by_file[where] = by_file.get(where, 0.0) + self_ns
            key = f"{name} [{source}]" if source else name
            by_op[key] = by_op.get(key, 0.0) + self_ns
    n = len(devices)
    first = min(s for d in devices for s, *_ in ops[d])
    last = max(s + du for d in devices for s, du, *_ in ops[d])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    dev0 = devices[0]
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": window_s if window_s else (last - first) * 1e-9,
        "by_layer_s": {k: v / n * 1e-9 for k, v in by_layer.items()},
        "by_file_s": {k: v / n * 1e-9 for k, v in sorted(
            by_file.items(), key=lambda kv: -kv[1])},
        "modules": sorted(modules.get(dev0, [])),
        "breakdown": {
            "device_ops": [[k[:120], v / n * 1e-9] for k, v in top_ops],
            "idle_gaps": idle_gaps(((s, s + d) for s, d, *_ in ops[dev0]),
                                   host_spans),
        },
    }


def reduce_dir(trace_dir: str, chips: int,
               window_s: Optional[float] = None) -> Dict:
    ops, modules, host = read_events(find_xplane(trace_dir))
    return reduce_events(ops, modules, host, chips, window_s)
