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
the rules of `layers/*.json` (one file a layer): a Pallas call by the
`name=` the program gave its kernel, which stands in the call's `op_name`
whatever the compiler calls the instruction; any other operation, and a
Pallas call no rule lists, by the source file and line of the Python frame
that made it. The trace's events carry only the HLO instruction's text; its
source is in the HLO module the profiler stores beside them (the `Hlo Proto` stat of
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
KERNEL_TARGET = "tpu_custom_call"
_TOKEN = re.compile(r"[A-Za-z_][A-Za-z0-9_.]*")


def data_files(kind: str, data: str = HERE) -> List[str]:
    """The `<kind>/*.json` files of the benchmark and, where a run is made
    under another directory of data files (the tests'), of that one too,
    in the order of their names."""
    found = {path for base in (HERE, data)
             for path in glob.glob(os.path.join(base, kind, "*.json"))}
    return sorted(found, key=lambda p: (os.path.basename(p), p))


def load_rules(data: str = HERE) -> List[Dict]:
    """One rule a file of `layers/`, in the order of the files' names."""
    rules = []
    for path in data_files("layers", data):
        with open(path) as f:
            rules.append(json.load(f))
    return rules


def tokens_of(op_name: str) -> List[str]:
    """The words of an `op_name`, outermost first. Scope and kernel names
    hold no `/`, `(`, `)` or space, so they are found whatever transform
    wraps them. Where the compiler joined the names of fused operations
    with `;`, the first one speaks."""
    return _TOKEN.findall(op_name.split(";", 1)[0])


def layer_of(name: str, source: str, rules: List[Dict],
             kernel: Iterable[str] = ()) -> str:
    """The layer of one device operation. `kernel` holds the tokens of a
    Pallas call's `op_name` (nothing for any other operation): the rule
    that lists one of them takes the call; else the source decides."""
    tokens = set(kernel)
    for rule in rules if tokens else ():
        if tokens.intersection(rule["kernels"]):
            return rule["layer"]
    for rule in rules:
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
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
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
    names], "computation": the one it stands in, "op": its opcode,
    "op_name": the name jax or the compiler gave it, or "", "body": a
    `while`'s body or ""}} from a module's HLO text.
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
        i = rhs.find('op_name="')
        body = _BODY.search(rhs) if op == "while" else None
        instrs[name] = info = {
            "op": op, "body": body.group(1) if body else "",
            "op_name": rhs[i + 9:rhs.find('"', i + 9)] if i >= 0 else "",
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


def pallas_calls(instrs: Dict[str, Dict],
                 kernel_names=frozenset()) -> Dict[str, List[str]]:
    """{instruction: the tokens of its `op_name`} of a module's Pallas
    calls: the custom calls whose target is `tpu_custom_call` (or, should
    the target's name change, any custom call that stands under one of
    `kernel_names`), and each fusion the compiler wraps one in together
    with the write of its result (`kind=kCustom`; the trace then times the
    fusion, not the call), which speaks with the wrapped call's name."""
    calls = {}
    for name, info in instrs.items():
        tokens = tokens_of(info["op_name"]) if info["target"] else ()
        if info["target"] == KERNEL_TARGET or (
                tokens and kernel_names.intersection(tokens)):
            calls[name] = tokens
    inside = {instrs[name]["computation"]: name for name in calls}
    for name, info in instrs.items():
        if info["op"] == "fusion":
            held = [inside[c] for c in info["calls"] if c in inside]
            if held:
                calls[name] = calls[held[0]]
    return calls


def instruction_facts(hlo_text: str, kernel_names=frozenset()
                      ) -> Dict[str, Tuple[str, str, Tuple[str, ...]]]:
    """{instruction name: (source "file:line", custom-call target, the
    `op_name`'s tokens if it is a Pallas call)} of one module's HLO text."""
    instrs = parse_hlo(hlo_text)
    calls = pallas_calls(instrs, kernel_names)
    return {name: (source, target, tuple(calls.get(name, ())))
            for name, (source, target) in inherit_sources(instrs).items()}


def module_sources(path: str, kernel_names=frozenset()) -> Dict[str, Dict]:
    """{module name: its `instruction_facts`} for a trace file."""
    from jax._src.lib import xla_client

    return {name: instruction_facts(
        xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            proto).to_string(), kernel_names)
        for name, proto in hlo_modules(path).items()}


def instruction_name(event_name: str) -> str:
    """`%fusion.5 = f32[..] fusion(...)` -> `fusion.5`."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def read_events(path: str, data: str = HERE):
    """(device_ops, modules, host_spans): device_ops {device: [(start_ns,
    dur_ns, instruction name (a custom call's led by its target), source
    "file:line", the `op_name`'s tokens of a Pallas call)]} of the `XLA
    Ops` lines,
    modules {device: [(start_ns, dur_ns, name)]}, host_spans [(start_ns,
    dur_ns, name)] of the harness's `bench.*` annotations."""
    import jax

    sources = module_sources(path, frozenset(
        k for rule in load_rules(data) for k in rule["kernels"]))
    profile = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in profile.planes:
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
                source, target, kernel = by_instr.get(name, ("", "", ()))
                if target and not name.startswith(target):
                    # a kernel the compiler named after its call site
                    name = f"{target}:{name}"
                found.append((e.start_ns, e.duration_ns, name, source,
                              kernel))
            if found:
                ops[plane.name] = found
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith("bench."))
    return ops, modules, host


def self_times(events: Iterable[Tuple]) -> List[Tuple]:
    """Each event `(start, dur, ...)` with its self time appended: its
    duration less the durations of the events nested directly inside it (one
    line of a trace: events nest or follow each other, they do not partly
    overlap)."""
    out: List[List] = []
    stack: List[int] = []  # indices into out
    for event in sorted(events, key=lambda e: (e[0], -e[1])):
        start, dur = event[0], event[1]
        while stack and start >= out[stack[-1]][0] + out[stack[-1]][1]:
            stack.pop()
        if stack:
            out[stack[-1]][-1] -= dur
        out.append([*event, dur])
        stack.append(len(out) - 1)
    return [tuple(e[:-1]) + (max(e[-1], 0.0),) for e in out]


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
                  window_s: Optional[float] = None, data: str = HERE) -> Dict:
    if not ops:
        raise RuntimeError("the trace holds no `XLA Ops` line of a TPU "
                           "device: nothing ran on the device, or the "
                           "profiler's names changed")
    rules = load_rules(data)
    devices = sorted(ops)[:chips]
    by_layer: Dict[str, float] = {}
    by_file: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    busy = 0.0
    for dev in devices:
        timed = self_times(ops[dev])
        busy += union_ns((s, s + d) for s, d, *_ in timed)
        for _, _, name, source, *kernel, self_ns in timed:
            layer = layer_of(name, source, rules, *kernel)
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
               window_s: Optional[float] = None, data: str = HERE) -> Dict:
    ops, modules, host = read_events(find_xplane(trace_dir), data)
    return reduce_events(ops, modules, host, chips, window_s, data)
