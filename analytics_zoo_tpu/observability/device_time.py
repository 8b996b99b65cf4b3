"""Device time by the program's own scopes.

The program names its work with `jax.named_scope` (`fit_step/...`,
`bert/block/ffn`, `moe/dispatch`), and XLA keeps that name in exactly one
place: the `metadata={op_name="..."}` of each instruction in the COMPILED
program's HLO text. A profiler capture names a device event by its
instruction alone. This module joins the two:

- `parse_op_name(op_name) -> (scope, direction)` reads an `op_name`;
- `scope_table(hlo_text) -> {module: {instruction: entry}}` reads a
  compiled program's text (`compiled.as_text()`; the trainer's
  `program_scopes(model)` makes it on request);
- `by_scope(per_instruction_seconds, table, depth)` sums seconds by
  (scope, direction);
- `reduce_capture(artifact_dir, table)` reads a capture's `.xplane.pb`
  and calls `by_scope`; `write_report` puts the result beside the capture
  as `device_time_by_scope.json`.

    python -m analytics_zoo_tpu.observability.device_time <artifact dir> [--depth n]

prints the rows that `fit(profile_steps=...)` or `POST /profile` left
beside their capture.

What a row means. A fusion is ONE device event that may hold the work of
several scopes (a weight-gradient product with the optimizer's update
fused behind it): all of its seconds go to the scope of its hero
instruction (a `convolution` or `dot` inside it, else its root), and the
row's `mixed_s` says how many of the row's seconds lie in fusions that
hold another scope too (the entry's `also`). `unscoped` is time in
instructions that no `jax.named_scope` of the program covers (the scan's
own slicing, copies XLA inserted). `unmatched` is time in events the
table does not hold: another program's (an evaluation, a warm-up), or a
program compiled before the table could be asked for. Shares are of the
summed time of the device's operations (containers such as `while` left
out, as `benchmark/trace_reduce.py` leaves them out) and add up to 100.
"""

from __future__ import annotations

import argparse
import collections
import glob
import hashlib
import json
import os
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

REPORT_FILE = "device_time_by_scope.json"
UNSCOPED, UNMATCHED = "unscoped", "unmatched"

Entry = Dict[str, object]             # {"scope", "direction", "also"}
Table = Dict[str, Dict[str, Entry]]   # module -> instruction -> entry
Seconds = Dict[Tuple[str, str], float]   # (module, instruction) -> s

# -- op_name -> (scope, direction) ------------------------------------------
# wrappers whose argument names a FUNCTION (`jit(epoch_run)`), not a scope
_FUNCTION_WRAPPERS = frozenset({"jit", "pjit", "xla_call", "named_call"})
# path parts that jax's own control flow and call primitives leave in the
# name stack; `rematted_computation` is read for the direction first
_STRUCTURE = re.compile(
    r"^(while|body|cond|body_fun|cond_fun|closed_call|core_call|checkpoint"
    r"|rematted_computation|remat\d?|custom_jvp_call|custom_vjp_call"
    r"|custom_vjp_call_jaxpr|custom_lin|branch_\d+_fun|shard_map)$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$", re.S)
_SCOPE_PART = re.compile(r"^[A-Za-z0-9_\-]+$")


def _split_path(path: str) -> List[str]:
    """`a/jvp(b/c)/d` -> [`a`, `jvp(b/c)`, `d`]: at `/` outside
    parentheses."""
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def _flatten(path: str, out: List[str], transforms: set) -> None:
    """The path's plain parts in order, wrappers opened and noted. jax
    writes a transformed scope as `transpose(jvp(scope))`, and under a
    checkpoint it restates the path so far inside such a wrapper
    (`a/b/transpose(jvp(a/b))/jvp()/checkpoint/c`): the restatement is
    dropped."""
    for part in _split_path(path):
        wrapped = _WRAPPED.match(part)
        if wrapped is None:
            out.append(part)
            continue
        name, inner = wrapped.groups()
        if name in _FUNCTION_WRAPPERS:
            continue
        transforms.add(name)
        at = len(out)
        _flatten(inner, out, transforms)
        said = out[at:]
        if said and out[max(0, at - len(said)):at] == said:
            del out[at:]


def parse_op_name(op_name: str) -> Tuple[str, str]:
    """(scope, direction) of an instruction's `op_name`.

    `jit(...)`, `vmap(...)`, `while/body`, `cond/branch_<n>_fun`,
    `closed_call`, `checkpoint` and the primitive's own name (the last
    part, where it is no wrapper) are taken away; `jvp(...)` and
    `transpose(jvp(...))` are opened. What is left is the path of the
    program's `jax.named_scope`s, or `unscoped`. A scope's part is made
    of letters, digits, `_` and `-`: an einsum's own `bhqd,bhkd->bhqk`
    and the qualified function names that jax's library code leaves in
    the stack (`Model.build`, `f.<locals>.g`) are none. The direction is
    `recompute` under a checkpoint's `rematted_computation`, else
    `backward` under a `transpose(...)`, else `forward` (which the
    optimizer's update reads too: it runs once, forwards). Where the
    chip's compiler made one instruction of several it joins their names
    with `;`: the first is read."""
    top = _split_path(op_name.split(";", 1)[0])
    if top and not _WRAPPED.match(top[-1]):
        top = top[:-1]
    parts: List[str] = []
    transforms: set = set()
    _flatten("/".join(top), parts, transforms)
    scope = "/".join(p for p in parts
                     if _SCOPE_PART.match(p) and not _STRUCTURE.match(p))
    direction = "recompute" if "rematted_computation" in parts else \
        "backward" if "transpose" in transforms else "forward"
    return scope or UNSCOPED, direction


# -- compiled HLO text -> table ---------------------------------------------
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:condition|body|to_apply|true_computation|false_computation"
    r"|calls)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
# instructions that are no device event of their own
_FREE = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast"})
# instructions that only contain others: their computations are walked
_CONTAINERS = frozenset({"while", "conditional", "call", "async-start"})
_HEROES = frozenset({"convolution", "dot"})
PALLAS_TARGET = "tpu_custom_call"


def _opcode(rest: str) -> str:
    """The opcode of `<type> <opcode>(<operands>), ...`. A type holds
    brackets of its own (tuples, layouts with tiling): the opcode starts
    after the first blank outside every bracket."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0].strip()
    return ""


class _Instruction(NamedTuple):
    name: str
    opcode: str
    op_name: Optional[str]
    rest: str            # the line after `name = `
    root: bool


def _computations(hlo_text: str):
    """(module name, entry computation, {computation: [_Instruction]})."""
    module, entry, comps, cur = "", None, {}, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            rest = m.group(3)
            named = _OP_NAME.search(rest)
            cur.append(_Instruction(
                m.group(2), _opcode(rest),
                named.group(1) if named else None, rest, bool(m.group(1))))
    return module, entry, comps


def _placed(op_name: Optional[str]) -> Tuple[str, str]:
    return parse_op_name(op_name) if op_name else (UNSCOPED, "forward")


def _fusion_entry(fused: list, own_op_name: Optional[str]) -> Entry:
    """A fusion's scope is its hero's: a product inside it if there is
    one, else its root; where that one stands under no scope (the scan's
    own update of its stacked output around the work), the scope most of
    its instructions stand under. `also` lists what else it holds."""
    hero = next((i for i in fused if i.opcode in _HEROES), None) \
        or next((i for i in fused if i.root), None)
    scoped = [p for p in (_placed(i.op_name) for i in fused if i.op_name)
              if p[0] != UNSCOPED]
    scope, direction = _placed(
        hero.op_name if hero and hero.op_name else own_op_name)
    if scope == UNSCOPED and scoped:
        scope, direction = collections.Counter(
            scoped).most_common(1)[0][0]
    return {"scope": scope, "direction": direction,
            "also": [list(a) for a in sorted(set(scoped)
                                             - {(scope, direction)})]}


def scope_table(hlo_text: str) -> Table:
    """{module: {instruction: {"scope", "direction", "also"}}} of a
    compiled program's text: every instruction that is a device event of
    its own, in the entry computation and in every computation that a
    `while`, a `conditional` or a `call` reaches from it. A Pallas kernel
    (a custom call to `tpu_custom_call`) keeps its kernel's name, which
    is its instruction's, as a leaf under its scope."""
    module, entry, comps = _computations(hlo_text)
    table: Dict[str, Entry] = {}
    todo, seen = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opcode, op_name, rest, _ in comps[comp]:
            if opcode in _FREE:
                continue
            if opcode in _CONTAINERS:
                todo += _CALLED.findall(rest)
                for group in _BRANCHES.findall(rest):
                    todo += [b.strip().lstrip("%")
                             for b in group.split(",")]
            if opcode == "fusion":
                called = _CALLED.search(rest)
                table[name] = _fusion_entry(
                    comps.get(called.group(1), []) if called else [],
                    op_name)
                continue
            scope, direction = _placed(op_name)
            target = _TARGET.search(rest) \
                if opcode == "custom-call" else None
            if target and target.group(1) == PALLAS_TARGET:
                # a named `pl.pallas_call` stands in the name stack too
                kernel = re.sub(r"\.\d+$", "", name)
                if scope.split("/")[-1] != kernel:
                    scope = kernel if scope == UNSCOPED \
                        else f"{scope}/{kernel}"
            table[name] = {"scope": scope, "direction": direction,
                           "also": []}
    _reroot(table)
    return {module: table} if table else {}


def _reroot(entries: Dict[str, Entry]) -> None:
    """jax drops the outer parts of the name stack in places: the primal
    loop of a differentiated `scan` reads `looplm/pass/...` where its
    transposed twin reads `fit_step/forward_backward/looplm/pass/...`. A
    scope that stands somewhere else in the program behind a prefix that
    starts otherwise is put behind the shortest such prefix, so that one
    part of the program reads under one path."""
    scopes = {e["scope"] for e in entries.values()} \
        | {a[0] for e in entries.values() for a in e["also"]}
    paths = [s.split("/") for s in scopes if s != UNSCOPED]
    home: Dict[str, str] = {}
    for parts in paths:
        found = [other[:i] for other in paths if other[0] != parts[0]
                 for i in range(1, len(other) - len(parts) + 1)
                 if other[i:i + len(parts)] == parts]
        if found:
            home["/".join(parts)] = "/".join(
                min(found, key=len) + parts)
    for entry in entries.values():
        entry["scope"] = home.get(entry["scope"], entry["scope"])
        also = {(home.get(a, a), d) for a, d in entry["also"]} \
            - {(entry["scope"], entry["direction"])}
        entry["also"] = [list(a) for a in sorted(also)]


def merge_tables(tables: Iterable[Table]) -> Table:
    """One table of several programs'. Where two programs share a
    module's name (two batch sizes of one served function) the later
    one's instructions win: their times then read under its scopes."""
    out: Table = {}
    for table in tables:
        for module, entries in table.items():
            out.setdefault(module, {}).update(entries)
    return out


def table_digest(table: Table) -> str:
    return hashlib.sha256(
        json.dumps(table, sort_keys=True).encode()).hexdigest()[:16]


# -- seconds by instruction -> rows -----------------------------------------
def _cut(scope: str, depth: Optional[int]) -> str:
    return scope if not depth or scope in (UNSCOPED, UNMATCHED) \
        else "/".join(scope.split("/")[:depth])


def at_depth(rows: List[Dict], depth: Optional[int]) -> List[Dict]:
    """Rows added up by the first `depth` parts of their scope, longest
    first."""
    grouped: Dict[Tuple[str, str], Dict] = {}
    for row in rows:
        key = (_cut(row["scope"], depth), row["direction"])
        into = grouped.setdefault(key, {
            "scope": key[0], "direction": key[1], "seconds": 0.0,
            "share_pct": 0.0, "ops": 0, "mixed_s": 0.0})
        for k in ("seconds", "share_pct", "ops", "mixed_s"):
            into[k] += row[k]
    return sorted(grouped.values(), key=lambda r: -r["seconds"])


def by_scope(per_instruction_seconds: Seconds, table: Table,
             depth: Optional[int] = None) -> List[Dict]:
    """Rows {scope, direction, seconds, share_pct, ops, mixed_s} of
    `{(module, instruction): seconds}` joined to `table`: `ops` counts the
    instructions, `mixed_s` the seconds in fusions that hold another
    scope too; events the table does not hold make the one row
    `unmatched`. A module the capture could not name (`""`) is looked up
    in every module of the table."""
    anywhere = {name: entry for entries in table.values()
                for name, entry in entries.items()}
    total = sum(per_instruction_seconds.values())
    rows = []
    for (module, name), seconds in per_instruction_seconds.items():
        entry = table.get(module, {} if module else anywhere).get(name)
        scope, direction, mixed = (UNMATCHED, "", False) if entry is None \
            else (entry["scope"], entry["direction"], bool(entry["also"]))
        rows.append({"scope": scope, "direction": direction,
                     "seconds": seconds,
                     "share_pct": 100.0 * seconds / total if total else 0.0,
                     "ops": 1, "mixed_s": seconds if mixed else 0.0})
    return at_depth(rows, depth)


# -- a capture -> seconds by instruction ------------------------------------
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_DEVICE_OP_LINE, _DEVICE_MODULE_LINE = "XLA Ops", "XLA Modules"
_HOST_PLANE = "/host:CPU"
_CPU_THUNK_LINE = re.compile(r"^tf_XLA(PjRt|Tfrt|Eigen)")
_CONTAINER_EVENT = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def find_xplane(artifact_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        artifact_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {artifact_dir}")
    return paths[-1]


def _module_name(event_name: str) -> str:
    """`jit_epoch_run(1234567)` on the modules' line -> `jit_epoch_run`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _module_at(modules: list, start: float) -> str:
    for name, m0, m1 in modules:
        if m0 <= start < m1:
            return name
    return ""


def read_capture(artifact_dir: str) -> Tuple[str, Seconds]:
    """(source, {(module, instruction): seconds}) of a capture. The
    source is `device` where the capture holds TPU planes: their `XLA
    Ops` line names an event by its instruction's whole text (the name
    stands before the `=`) and carries no module: that is the event of
    the `XLA Modules` line that holds it. Without a device plane the CPU backend's thunk events
    (`hlo_op`, `hlo_module`) stand in, and the source says `cpu_thunks`:
    a path to run end to end, never a device's number."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(find_xplane(artifact_dir)).planes)
    seconds: Seconds = {}

    def add(module, name, dur_ns):
        if dur_ns > 0 and not _CONTAINER_EVENT.match(name):
            key = (_module_name(module), name)
            seconds[key] = seconds.get(key, 0.0) + dur_ns / 1e9

    device = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    for plane in device:
        lines = {line.name: line for line in plane.lines}
        modules = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in lines[_DEVICE_MODULE_LINE].events] \
            if _DEVICE_MODULE_LINE in lines else []
        for ev in (lines[_DEVICE_OP_LINE].events
                   if _DEVICE_OP_LINE in lines else ()):
            add(_module_at(modules, ev.start_ns),
                ev.name.partition(" = ")[0].lstrip("%"), ev.duration_ns)
    if seconds:
        return "device", seconds
    for plane in planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            if not _CPU_THUNK_LINE.match(line.name):
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" in stats:
                    add(stats.get("hlo_module", ""), stats["hlo_op"],
                        ev.duration_ns)
    return ("cpu_thunks" if seconds else "none"), seconds


def reduce_capture(artifact_dir: str, table: Table,
                   depth: Optional[int] = None) -> Dict:
    """A capture's device time by scope: {device_source, total_s,
    table_digest, rows}."""
    source, seconds = read_capture(artifact_dir)
    return {"device_source": source,
            "total_s": sum(seconds.values()),
            "table_digest": table_digest(table),
            "rows": by_scope(seconds, table, depth)}


def write_report(artifact_dir: str, table: Table) -> Dict:
    """`reduce_capture` at full depth, written beside the capture as
    `device_time_by_scope.json`."""
    report = reduce_capture(artifact_dir, table)
    if not table:
        report["note"] = ("the program gave no table of scopes: every "
                          "event reads unmatched")
    elif report["device_source"] != "device":
        report["note"] = ("no device plane in the capture: the CPU "
                          "backend's thunk events stand in")
    with open(os.path.join(artifact_dir, REPORT_FILE), "w") as fh:
        json.dump(report, fh)
    return report


def format_rows(rows: List[Dict]) -> str:
    lines = [f"{'share%':>8} {'seconds':>11} {'mixed_s':>11} {'ops':>6}  "
             "scope [direction]"]
    for r in rows:
        lines.append(
            f"{r['share_pct']:8.3f} {r['seconds']:11.6f} "
            f"{r['mixed_s']:11.6f} {r['ops']:6d}  {r['scope']}"
            + (f" [{r['direction']}]" if r["direction"] else ""))
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="device time of a profiler capture by the program's "
                    "own scopes")
    p.add_argument("artifact_dir",
                   help="a capture's directory holding "
                        f"{REPORT_FILE} (fit(profile_steps=...), "
                        "POST /profile)")
    p.add_argument("--depth", type=int, default=None,
                   help="add rows up by the first n parts of their scope")
    args = p.parse_args(argv)
    path = os.path.join(args.artifact_dir, REPORT_FILE)
    if not os.path.exists(path):
        print(f"no {REPORT_FILE} under {args.artifact_dir}: the capture "
              "was taken without a program to ask for its scopes",
              file=sys.stderr)
        return 1
    with open(path) as fh:
        report = json.load(fh)
    print(f"device_source={report['device_source']} "
          f"total_s={report['total_s']:.6f} "
          f"table={report['table_digest']}")
    if "note" in report:
        print(report["note"])
    print(format_rows(at_depth(report["rows"], args.depth)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
