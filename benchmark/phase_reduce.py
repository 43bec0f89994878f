"""Device time by the program's own vocabulary of scopes, and the program's
host spans, from the traced run's `.xplane.pb`.

`trace_reduce.py` times the engine from outside: it gives a device operation
to a layer by the source file of the Python frame that made it. This reader
gives it to what the program says it was doing: every HLO instruction's
`op_name` (in the HLO module the profiler stores beside the events, as the
sources are) holds the `jax.named_scope`s it was traced under, wrapped in
the transforms above them (`vmap(engine_probe)`,
`transpose(jvp(phase_dense_fwd_bwd))`).

The vocabulary is data: `phases.json` (the program's, which
tests/test_scopes.py holds equal to deeprec_tpu/utils/scopes.py) and every
`phases/*.json` beside it, merged on load. It is a set of GROUPS of scope
names, each with a pick: of an `op_name`'s tokens the outermost (or
innermost) one that is a name of the group is the instruction's pick in
that group. Four groups have keys of their own in a vocabulary file:
`phases` (with `exchange`, prefixes of further phase names; outermost),
`stages`, `rows` and `kernels` (innermost); a file declares any further
group under `groups`: `{"<group>": {"pick": "innermost" | "outermost",
"names": [...]}}`. An instruction the compiler made itself carries no name
of jax's and inherits by `trace_reduce.inherit_sources`, handed the scopes
in place of the sources; one the program made outside every phase stays
unphased. Times are self times, so the phases and the unphased rest add up
to the busy time.

What a per-layer metric reads stands in its own module as `READS`, one of
(`reading` below):
  {"phase": name}   device self time whose pick of `phase` is that name
                    (`unphased`: none)
  {"stage": name}   likewise of `stage`
  {"scope": name}   likewise of whichever group holds the name
  {"rows": "kernel" | "wrapper"}  under a `rows` pick: the Pallas calls,
                    or the rest
  {"kernel": name}  the Pallas calls whose pick of `kernel` is that name,
                    wherever they stand
  {"loop": name}    executions of the bodies of the `while`s whose pick (in
                    the name's group) is that name, a step
  {"span": name}    host time inside the program's spans of that name

The scopes have to be in the `op_name`s: `enable_compile_cache()` sets
`jax_traceback_in_locations_limit=1` for that. (With
`jax_include_full_tracebacks_in_locations=False` an `op_name` is the bare
primitive and the scope path is left in the function name of the stack
frame; inside a loop's body that path starts at the body, and the compiler
rebuilds the loops round the vmapped row kernels without metadata, so half
a step cannot be given to its stage. Tried on the chip, PERF.md section 6.)

The file is parsed once a run and held to the harness's own reduction of
the same file: a busy time that differs is another run's trace and gives no
reading. Nor does a name of a group of which the trace holds no name at all
(a program from before it wrote that group's scopes): a reader never
returns 0 for what the program did not say.
"""
from __future__ import annotations

import bisect
import glob
import importlib
import json
import os
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(HERE), "benchmark_out", "trace")
UNPHASED = "unphased"
# the groups that have keys of their own in a vocabulary file: (key of the
# names, group, pick)
_BUILT_IN = (("phases", "phase", "outermost"), ("stages", "stage", "innermost"),
             ("rows", "rows", "innermost"), ("kernels", "kernel", "innermost"))
KINDS = ("phase", "stage", "scope", "rows", "kernel", "loop", "span")


def load_vocabulary(data: str = HERE) -> Dict:
    """The vocabulary files merged: `phases.json`, then `phases/*.json` in
    the order of their names (the benchmark's, and those of `data` where a
    run is made under another directory of data files). Lists are joined,
    `groups` group by group; `note` and `reads` are dropped."""
    paths = [os.path.join(HERE, "phases.json")]
    paths += trace_reduce.data_files("phases", data)
    merged: Dict = {"groups": {}}
    for path in paths:
        with open(path) as f:
            part = json.load(f)
        part.pop("note", None)
        part.pop("reads", None)   # phases.json keeps the key, empty
        for group, spec in part.pop("groups", {}).items():
            have = merged["groups"].setdefault(
                group, {"pick": spec["pick"], "names": []})
            if have["pick"] != spec["pick"]:
                raise ValueError(f"{path}: group {group!r} is picked "
                                 f"{have['pick']} elsewhere")
            have["names"] += [n for n in spec["names"]
                              if n not in have["names"]]
        for key, value in part.items():
            if isinstance(value, list):
                have = merged.setdefault(key, [])
                have += [v for v in value if v not in have]
            elif merged.setdefault(key, value) != value:
                raise ValueError(f"{path}: {key!r} is {merged[key]!r} "
                                 "elsewhere")
    return merged


class Group(NamedTuple):
    name: str
    outermost: bool
    names: frozenset
    prefixes: Tuple[str, ...]   # beginnings of further names of the group


def groups_of(vocab: Dict) -> Tuple[Group, ...]:
    """The groups a vocabulary declares, the four with keys of their own
    first (a dict as `deeprec_tpu.utils.scopes.vocabulary()` gives it has
    only those)."""
    out = []
    extra = dict(vocab.get("groups", {}))
    for key, group, pick in _BUILT_IN:
        more = extra.pop(group, {"names": []})
        out.append(Group(
            group, pick == "outermost",
            frozenset(vocab.get(key, ())) | frozenset(more["names"]),
            tuple(vocab.get("exchange", ())) if group == "phase" else ()))
    for group, spec in extra.items():
        if spec["pick"] not in ("innermost", "outermost"):
            raise ValueError(f"group {group!r}: pick {spec['pick']!r}")
        out.append(Group(group, spec["pick"] == "outermost",
                         frozenset(spec["names"]), ()))
    return tuple(out)


class Scope(tuple):
    """An instruction's pick in each group, in the groups' order ("" where
    it stands under no name of the group); `scope.stage` is the pick of the
    group `stage`."""

    def __new__(cls, picks, groups: Tuple[str, ...]):
        self = super().__new__(cls, picks)
        self.groups = groups
        return self

    def __getattr__(self, group: str) -> str:
        try:
            return self[self.groups.index(group)]
        except ValueError:
            raise AttributeError(group) from None


def scope_of(op_name: str, vocab: Dict) -> Scope:
    """The scopes an `op_name` holds, group by group."""
    return _scope_of(op_name, groups_of(vocab))


def _scope_of(op_name: str, groups: Tuple[Group, ...]) -> Scope:
    tokens = trace_reduce.tokens_of(op_name)
    picks = []
    for g in groups:
        order = tokens if g.outermost else reversed(tokens)
        picks.append(next(
            (t for t in order if t in g.names
             or (g.prefixes and t.startswith(g.prefixes))), ""))
    return Scope(picks, tuple(g.name for g in groups))


class Module(NamedTuple):
    scopes: Dict[str, Scope]       # instruction -> its scopes, inherited
    kernels: frozenset             # instructions that are Pallas calls
    loop_bodies: Dict[str, str]    # instruction -> the `while` it is a
                                   # direct member of the body of


def module_scopes(hlo_text: str, vocab: Dict) -> Module:
    """Scopes of every instruction of one module's HLO text."""
    groups = groups_of(vocab)
    names = tuple(g.name for g in groups)
    instrs = trace_reduce.parse_hlo(hlo_text)
    for info in instrs.values():
        # a name of jax's own (`jit(step)/...`) speaks for itself, with or
        # without a scope in it; no name, or one the compiler gave (a bare
        # `scatter-add`, a parameter's path), inherits
        info["source"] = ("|" + "|".join(_scope_of(info["op_name"], groups))
                          if "/" in info["op_name"] else "")
    inherited = trace_reduce.inherit_sources(instrs)
    scopes = {name: Scope(key[1:].split("|") if key else [""] * len(names),
                          names)
              for name, (key, _) in inherited.items()}
    kernel_names = next(g.names for g in groups if g.name == "kernel")
    kernels = frozenset(trace_reduce.pallas_calls(instrs, kernel_names))
    loops = {info["body"]: name for name, info in instrs.items()
             if info["op"] == "while" and info["body"]}
    loop_bodies = {name: loops[info["computation"]]
                   for name, info in instrs.items()
                   if info["computation"] in loops}
    return Module(scopes, kernels, loop_bodies)


def read_modules(path: str, vocab: Dict, wanted) -> Dict[str, Module]:
    """The modules of the trace file that ran on a device (`wanted`, by
    the names of the `XLA Modules` line); the host's are left unparsed."""
    from jax._src.lib import xla_client

    out = {}
    for name, proto in trace_reduce.hlo_modules(path).items():
        if name not in wanted:
            continue
        module = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            proto)
        out[name] = module_scopes(module.to_string(), vocab)
    return out


def read_trace(path: str, vocab: Dict):
    """(modules {name: Module}, device_ops {device: [(start_ns, dur_ns,
    instruction, module name)]} of the `XLA Ops` lines, host [(name,
    start_ns, dur_ns, step_num or -1)] of the program's own spans)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    names = set(vocab["host_spans"]) | {vocab["step_span"]}
    ops: Dict[str, List[Tuple]] = {}
    host: List[Tuple] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE not in lines:
                continue
            mods = sorted((e.start_ns, e.name) for e in
                          lines[trace_reduce.MODULES_LINE].events
                          ) if trace_reduce.MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            found = []
            for e in lines[trace_reduce.OPS_LINE].events:
                k = bisect.bisect_right(starts, e.start_ns) - 1
                found.append((e.start_ns, e.duration_ns,
                              trace_reduce.instruction_name(e.name),
                              mods[k][1] if k >= 0 else ""))
            ops[plane.name] = found
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.duration_ns,
                     int(dict(e.stats).get("step_num", -1)))
                    for e in line.events if e.name in names)
    ran = {mod for found in ops.values() for *_, mod in found}
    return read_modules(path, vocab, ran), ops, host


def reduce_events(modules: Dict[str, Module], ops: Dict[str, List[Tuple]],
                  host: List[Tuple], chips: int, vocab: Dict) -> Dict:
    """Seconds of device self time (a mean over `chips` devices) by phase,
    by stage, by any scope name, under a `rows` pick by kernel and wrapper,
    by kernel name; the executions of the loops' bodies by the loops'
    scopes; the program's host spans."""
    by_phase: Dict[str, float] = {}
    by_stage: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    by_pair: Dict[str, float] = {}
    by_kernel: Dict[str, float] = {}
    by_kernel_name: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    rows = {"kernel": 0.0, "wrapper": 0.0}
    kernels_all = busy = 0.0
    passes: Dict[Tuple[str, str, str, str], int] = {}
    devices = sorted(ops)[:chips]
    groups = groups_of(vocab)
    names = tuple(g.name for g in groups)
    nowhere = Scope([""] * len(names), names)
    for dev in devices:
        timed = trace_reduce.self_times(ops[dev])
        busy += trace_reduce.union_ns((s, s + d) for s, d, *_ in timed)
        for _, _, instr, mod, self_ns in timed:
            module = modules.get(mod)
            scope = module.scopes.get(instr, nowhere) if module else nowhere
            phase = scope.phase or UNPHASED
            by_phase[phase] = by_phase.get(phase, 0.0) + self_ns
            if scope.stage:
                by_stage[scope.stage] = by_stage.get(
                    scope.stage, 0.0) + self_ns
            for pick in scope:
                if pick:
                    by_scope[pick] = by_scope.get(pick, 0.0) + self_ns
            pair = f"{phase}/{scope.stage or '-'}"
            by_pair[pair] = by_pair.get(pair, 0.0) + self_ns
            op = f"{instr} [{pair}/{scope.rows or '-'}]"
            by_op[op] = by_op.get(op, 0.0) + self_ns
            is_kernel = bool(module) and instr in module.kernels
            if is_kernel:
                kernels_all += self_ns
                label = f"{phase}/{scope.kernel or instr}"
                by_kernel[label] = by_kernel.get(label, 0.0) + self_ns
                if scope.kernel:
                    by_kernel_name[scope.kernel] = by_kernel_name.get(
                        scope.kernel, 0.0) + self_ns
            if scope.rows:
                rows["kernel" if is_kernel else "wrapper"] += self_ns
            if module and instr in module.loop_bodies:
                key = (dev, mod, module.loop_bodies[instr], instr)
                passes[key] = passes.get(key, 0) + 1
    # a loop's passes: the executions of one instruction of its body (each
    # direct member runs once a pass; the commonest count is taken), booked
    # to every scope the loop stands under
    per_loop: Dict[Tuple, List[int]] = {}
    for key, count in passes.items():
        per_loop.setdefault(key[:3], []).append(count)
    n = max(len(devices), 1)
    loop_passes: Dict[str, float] = {}
    for (_, mod, loop), counts in per_loop.items():
        for pick in modules[mod].scopes.get(loop, ()):
            if pick:
                loop_passes[pick] = loop_passes.get(pick, 0.0) + max(
                    set(counts), key=counts.count) / n
    sec = lambda d: {k: v / n * 1e-9 for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])}
    spans: Dict[str, Dict] = {}
    for name, _, dur, _ in host:
        rec = spans.setdefault(name, {"count": 0, "total_s": 0.0})
        rec["count"] += 1
        rec["total_s"] += dur * 1e-9
    return {
        # the groups the program speaks at all: a reading of a name of any
        # other group is no reading (a program from before it wrote them)
        "groups": groups,
        "groups_seen": {g.name for g in groups if any(
            getattr(s, g.name) for m in modules.values()
            for s in m.scopes.values())},
        "busy_s": busy / n * 1e-9,
        "by_phase_s": sec(by_phase), "by_stage_s": sec(by_stage),
        "by_scope_s": sec(by_scope),
        "by_phase_and_stage_s": sec(by_pair), "rows_s": sec(rows),
        "kernels_s": kernels_all / n * 1e-9, "by_kernel_s": sec(by_kernel),
        "by_kernel_name_s": sec(by_kernel_name),
        "top_ops_s": dict(list(sec(by_op).items())[:24]),
        "loop_passes": loop_passes,
        "host_spans": spans, "step_span": vocab["step_span"],
        "train_steps": sorted((num, start, dur) for name, start, dur, num
                              in host if name == vocab["step_span"]),
    }


def reduce_file(path: str, chips: int, data: str = HERE) -> Dict:
    vocab = load_vocabulary(data)
    modules, ops, host = read_trace(path, vocab)
    return reduce_events(modules, ops, host, chips, vocab)


_MEMO: Dict[Tuple, Dict] = {}


def for_run(ctx: Dict) -> Optional[Dict]:
    """The reduction of this run's trace, or None where there is none to
    read: no file, a program without any scope, or a file whose busy time
    is not the one the harness reduced."""
    if not ctx.get("trace") or not ctx.get("traced_steps"):
        return None
    try:
        path = trace_reduce.find_xplane(ctx.get("trace_dir") or TRACE_DIR)
    except FileNotFoundError:
        return None
    stat = os.stat(path)
    data = ctx.get("data", HERE)
    key = (path, stat.st_mtime_ns, stat.st_size, ctx["chips"], data)
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = reduce_file(path, ctx["chips"], data)
    red = _MEMO[key]
    want = ctx["trace"]["busy_s"]
    if not red["groups_seen"] or abs(red["busy_s"] - want) > 0.01 * want:
        return None
    return red


def read_as(red: Dict, steps: int, reads: Dict[str, str]) -> Optional[float]:
    """What a metric's `READS` names, of a reduction over `steps` steps:
    device ms a step, passes a step, or host ms a step."""
    (kind, name), = reads.items()
    ms = 1e3 / steps
    if kind not in KINDS:
        raise ValueError(f"READS of an unknown kind: {reads}; the kinds "
                         f"are {KINDS}")
    if kind != "span":
        group = {"phase": "phase", "stage": "stage", "rows": "rows",
                 "kernel": "kernel"}.get(kind) or next(
            (g.name for g in red["groups"] if name in g.names
             or (g.prefixes and name.startswith(g.prefixes))), None)
        if group not in red["groups_seen"]:
            return None
    if kind == "phase":
        return red["by_phase_s"].get(name, 0.0) * ms
    if kind == "stage":
        return red["by_stage_s"].get(name, 0.0) * ms
    if kind == "scope":
        return red["by_scope_s"].get(name, 0.0) * ms
    if kind == "rows":
        return red["rows_s"][name] * ms
    if kind == "kernel":
        return red["by_kernel_name_s"].get(name, 0.0) * ms
    if kind == "loop":
        return red["loop_passes"].get(name, 0.0) / steps
    if name == red["step_span"] and red["train_steps"]:
        return sum(d for _, _, d in red["train_steps"]) * 1e-6 / steps
    if name != red["step_span"] and name in red["host_spans"]:
        return red["host_spans"][name]["total_s"] * ms
    return None   # no such span: a trace without the program's


def reading(ctx: Dict, reads: Dict[str, str]) -> Optional[float]:
    red = for_run(ctx)
    return read_as(red, ctx["traced_steps"], reads) if red else None


def metric_reads() -> Dict[str, Dict[str, str]]:
    """{metric: its READS} of the benchmark's readers that read through
    this module (benchmark/layer_metrics/*.py)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "[a-z]*.py"))):
        name = os.path.basename(path)[:-3]
        reads = getattr(importlib.import_module(
            f"benchmark.layer_metrics.{name}"), "READS", {})
        if len(reads) == 1 and next(iter(reads)) in KINDS:
            out[name] = reads
    return out


def main(argv: List[str]) -> int:
    """python3 -m benchmark.phase_reduce <trace dir | .xplane.pb> [chips]:
    the breakdown of any trace of the program (a `--timeline` window too),
    in device ms a step, a step being one `deeprec.train_step` span."""
    path = argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    red = reduce_file(path, int(argv[2]) if len(argv) > 2 else 1)
    steps = max(len(red["train_steps"]), 1)
    ms = lambda d: {k: round(v * 1e3 / steps, 4)  # noqa: E731
                    for k, v in d.items()}
    metrics = {name: read_as(red, steps, reads)
               for name, reads in metric_reads().items()}
    print(json.dumps({
        "file": path, "steps": steps,
        "groups_seen": sorted(red["groups_seen"]),
        "busy_ms_per_step": round(red["busy_s"] * 1e3 / steps, 4),
        "kernels_ms_per_step": round(red["kernels_s"] * 1e3 / steps, 4),
        "by_phase_ms_per_step": ms(red["by_phase_s"]),
        "by_stage_ms_per_step": ms(red["by_stage_s"]),
        "by_phase_and_stage_ms_per_step": ms(red["by_phase_and_stage_s"]),
        "rows_ms_per_step": ms(red["rows_s"]),
        "by_kernel_ms_per_step": ms(red["by_kernel_s"]),
        "top_ops_ms_per_step": ms(red["top_ops_s"]),
        "loop_passes_per_step": {k: round(v / steps, 4)
                                 for k, v in red["loop_passes"].items()},
        "metrics": {k: round(v, 4) for k, v in metrics.items()
                    if v is not None},
        "host_spans": red["host_spans"],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
