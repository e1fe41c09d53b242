"""Device time by the model's named scopes, in a serve cell's traced window.

The model tags its ops with ``jax.named_scope``: ``embed``, ``layers``
around the layer scan, a mixer scope (``attn``, ``ssd``, ``rglru``) and
``ffn`` inside it, ``kv_write`` around the cache writes of attention, and
``logits``.  The compiler keeps the scope path in each instruction's
``op_name`` metadata, and the profiler's ``XLA Ops`` events carry the
instruction's name.  So:

* ``program_text`` compiles the decode program (a cache hit where it
  has run) and names the parameters that hold the decode state;
* ``hlo_scopes`` reads its HLO text into a map from instruction name to
  class;
* ``extract_modules`` reads the runs of each program (the ``XLA Modules``
  line of each device) from the trace file, since instruction names repeat
  across programs;
* ``reduce`` gives the self time of the decode program's ops in each
  class, inside the window.

Classes: ``kv_write``; the mixer's scope; ``ffn``; ``embed``;
``logits``; and, for an op under ``layers`` with no child scope (the
layer scan's own per-layer slices and write-backs), ``layer_state`` where
it moves the decode state and ``layer_weights`` where it slices the
stacked weights.  Which of the two is read from the data the op moves:
its operands are followed back to the scan's loop-carried tuple, and each
element of that tuple to the program's parameter it starts from, or to
the program's output it ends in.  A parameter the caller names as state,
or an output of the scan (the new state it stacks), is state; any other
parameter is weights.  ``other`` holds loop control and ops with no scope.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench.trace_reduce import WINDOW_SPAN, op_name, self_times

SCOPES = ("kv_write", "attn", "ssd", "rglru", "ffn", "embed", "logits")
#: classes that hold the decode state's own movement
STATE = ("kv_write", "layer_state")
#: every class ``reduce`` reports
CLASSES = ("layer_state", "layer_weights") + SCOPES + ("other",)
#: ops that only route values or run a loop: never any scope's work
CONTROL = frozenset({"while", "conditional", "call", "tuple",
                     "get-tuple-element", "parameter", "constant",
                     "bitcast", "after-all", "opt-barrier"})
#: ops that pass a value on unchanged, followed when tracing where the
#: data of a layer op comes from
_PASS = frozenset({"bitcast", "copy", "copy-start", "copy-done",
                   "get-tuple-element", "reshape"})

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_class(name: str | None) -> str | None:
    """The class of one ``op_name`` path, or None where it names no
    scope of the model's; ``layers`` where it names the layer scan and
    no scope inside it."""
    if not name:
        return None
    parts = name.split("/")
    for scope in SCOPES:
        if scope in parts:
            return scope
    # the scan's loop condition is control, not the layers' work
    return "layers" if "layers" in parts and "cond" not in parts else None


def _computations(text: str) -> tuple[dict[str, list[dict]], set[str], str]:
    """Instructions by computation, the computations that fusions call
    (their instructions never run as ops of their own), and the name of
    the entry computation."""
    comps: dict[str, list[dict]] = {}
    fused: set[str] = set()
    entry = ""
    current = None
    for line in text.splitlines():
        if line.startswith("}"):
            current = None
            continue
        if not line.startswith(" ") and line.rstrip().endswith("{") and "(" in line:
            words = line.split()
            name = (words[1] if words[0] == "ENTRY" else words[0]).lstrip("%")
            if words[0] == "ENTRY":
                entry = name
            current = comps.setdefault(name, [])
            continue
        m = _INSTR.match(line) if current is not None else None
        if not m:
            continue
        rest = " " + m.group(3)
        opcode = _OPCODE.search(rest)
        meta = _OP_NAME.search(rest)
        calls = _CALLS.findall(rest)
        args = ""
        if opcode:
            args = rest[opcode.end():].split(")", 1)[0]
            if opcode.group(1) == "fusion":
                fused.update(calls)
        index = _INDEX.search(rest)
        body = _BODY.search(rest)
        current.append({"name": m.group(2),
                        "root": bool(m.group(1)),
                        "opcode": opcode.group(1) if opcode else "",
                        "op_name": meta.group(1) if meta else None,
                        "calls": calls,
                        "operands": _OPERAND.findall(args),
                        "number": int(args) if args.strip().isdigit() else None,
                        "index": int(index.group(1)) if index else None,
                        "body": body.group(1) if body else None})
    return comps, fused, entry


def _fused_classes(comps: dict, name: str, seen: set[str]) -> list[str]:
    out = []
    for ins in comps.get(name, ()):
        cls = scope_class(ins["op_name"])
        if cls is not None:
            out.append(cls)
        for callee in ins["calls"]:
            if callee not in seen:
                seen.add(callee)
                out += _fused_classes(comps, callee, seen)
    return out


def _pick(classes: list[str]) -> str:
    """A fusion with no scope of its own takes the most specific one of
    the ops it fuses: a cache write, then a model scope, then the bare
    layer scope."""
    for cls in ("kv_write",) + SCOPES[1:]:
        if cls in classes:
            return cls
    return "layers" if "layers" in classes else "other"


class _Program:
    """Where the data of a compiled program's layer ops comes from."""

    def __init__(self, comps: dict, entry: str, state_params):
        self.entry = entry
        self.state_params = frozenset(state_params)
        self.by_name = {c: {i["name"]: i for i in instrs}
                        for c, instrs in comps.items()}
        self.users: dict[str, dict[str, list[dict]]] = {}
        #: loop body -> (the computation that runs the loop, the loop)
        self.loop_of: dict[str, tuple[str, dict]] = {}
        for comp, instrs in comps.items():
            users = self.users.setdefault(comp, defaultdict(list))
            for ins in instrs:
                for o in ins["operands"]:
                    users[o].append(ins)
                if ins["opcode"] == "while" and ins["body"]:
                    self.loop_of[ins["body"]] = (comp, ins)
        params = {i["number"] for i in comps.get(entry, ())
                  if i["opcode"] == "parameter"}
        if not self.state_params <= params:
            raise ValueError("the state names parameters the program lacks")
        self._kinds: dict[tuple[str, int], str | None] = {}

    def _value_kind(self, comp: str, name: str) -> str | None:
        """``state``, ``weights`` or None, for the value ``name`` of
        ``comp`` followed back through ops that pass it on."""
        ins = self.by_name[comp].get(name)
        while ins is not None:
            op = ins["opcode"]
            if op == "parameter":
                if comp != self.entry:
                    return None
                return ("state" if ins["number"] in self.state_params
                        else "weights")
            if op == "get-tuple-element" and self._is_loop_param(comp, ins):
                return self.element_kind(comp, ins["index"])
            if op not in _PASS or not ins["operands"]:
                return None
            ins = self.by_name[comp].get(ins["operands"][0])
        return None

    def _is_loop_param(self, comp: str, ins: dict) -> bool:
        src = self.by_name[comp].get(ins["operands"][0]) if ins["operands"] else None
        return (src is not None and src["opcode"] == "parameter"
                and comp in self.loop_of)

    def element_kind(self, body: str, k: int) -> str | None:
        """The kind of element ``k`` of a loop's carried tuple: that of
        the value it starts from, else ``state`` where the loop's result
        at ``k`` leaves the program (a stacked output of the scan)."""
        key = (body, k)
        if key not in self._kinds:
            self._kinds[key] = None          # a cycle reads as no kind
            comp, loop = self.loop_of[body]
            init = self.by_name[comp].get(loop["operands"][0])
            kind = None
            if init is not None and init["opcode"] == "tuple" \
                    and k < len(init["operands"]):
                kind = self._value_kind(comp, init["operands"][k])
            if kind is None and self._leaves(comp, loop["name"], k):
                kind = "state"
            self._kinds[key] = kind
        return self._kinds[key]

    def _leaves(self, comp: str, loop: str, k: int) -> bool:
        """Whether element ``k`` of the loop's result reaches the entry
        computation's root, passed on unchanged."""
        if comp != self.entry:
            return False
        users = self.users[comp]
        todo = [u for u in users[loop] if u["opcode"] == "get-tuple-element"
                and u["index"] == k]
        seen = set()
        while todo:
            ins = todo.pop()
            if ins["name"] in seen:
                continue
            seen.add(ins["name"])
            if ins["root"]:
                return True
            todo += [u for u in users[ins["name"]]
                     if u["opcode"] in _PASS or u["root"]]
        return False

    def layer_class(self, comp: str, ins: dict, base: dict) -> str:
        """``layer_state``, ``layer_weights`` or ``other`` for an op under
        the bare layer scope, from the kinds of data it reads."""
        kinds = set()
        todo, seen = list(ins["operands"]), set()
        while todo:
            name = todo.pop()
            if name in seen:
                continue
            seen.add(name)
            src = self.by_name[comp].get(name)
            if src is None:
                continue
            kind = self._value_kind(comp, name)
            if kind is not None:
                kinds.add(kind)
            elif src["opcode"] in _PASS or base.get(src["name"]) == "layers":
                todo += src["operands"]
        if "state" in kinds:
            return "layer_state"
        return "layer_weights" if "weights" in kinds else "other"


def hlo_scopes(text: str, state_params) -> dict[str, str]:
    """Instruction name to class, for every instruction that runs as an op
    of its own (those outside fused computations).  ``state_params`` are
    the numbers of the entry computation's parameters that hold the
    decode state (``program_text`` gives them)."""
    comps, fused, entry = _computations(text)
    base: dict[str, str] = {}
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for ins in instrs:
            if ins["opcode"] in CONTROL:
                base[ins["name"]] = "other"
                continue
            cls = scope_class(ins["op_name"])
            if cls is None and ins["opcode"] == "fusion":
                cls = _pick(_fused_classes(comps, ins["calls"][0], set())
                            if ins["calls"] else [])
            base[ins["name"]] = cls or "other"
    program = _Program(comps, entry, state_params)
    out = dict(base)
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for ins in instrs:
            if base.get(ins["name"]) == "layers":
                out[ins["name"]] = program.layer_class(comp, ins, base)
    return out


def program_text(jitted, *args, state_arg: int) -> tuple[str, frozenset[int]]:
    """The compiled HLO text of ``jitted`` on ``args``, and the numbers of
    its parameters that hold the leaves of ``args[state_arg]``.  Raises
    where the program dropped an argument, since the numbers would then
    shift."""
    import jax

    text = jitted.lower(*args).compile().as_text()
    counts = [len(jax.tree.leaves(a)) for a in args]
    comps, _, entry = _computations(text)
    n_params = sum(1 for i in comps[entry] if i["opcode"] == "parameter")
    if n_params != sum(counts):
        raise ValueError(f"the program has {n_params} parameters for "
                         f"{sum(counts)} argument leaves")
    first = sum(counts[:state_arg])
    return text, frozenset(range(first, first + counts[state_arg]))


def module_name(text: str) -> str:
    """The compiled module's name, from the first line of its HLO text."""
    first = text.split("\n", 1)[0].split()
    if len(first) < 2 or first[0] != "HloModule":
        raise ValueError("not the text of a compiled HLO module")
    return first[1].rstrip(",")


def extract_modules(path: str) -> dict[str, list]:
    """Each device's program runs, ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    out: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            runs = out.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs.extend((ev.name, ev.start_ns, ev.duration_ns)
                                for ev in line.events)
    return out


def module_runs(module: str, runs: list) -> tuple[list[float], list[float]]:
    """Starts and ends of the runs of ``module``, in order; a run's name
    may add the program's id in parentheses."""
    spans = sorted((s, s + d) for name, s, d in runs
                   if name == module or name.startswith(module + "("))
    return [a for a, _ in spans], [b for _, b in spans]


def _inside(t: float, starts: list[float], ends: list[float]) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < ends[i]


def reduce(record: dict, scopes: dict[str, str], module: str) -> dict | None:
    """Seconds of device self time per class, averaged over the devices,
    of the ops that ran inside ``module`` and inside the traced window.
    None where the map names no scope of the model's (a program built
    without them)."""
    if all(cls == "other" for cls in scopes.values()):
        return None
    marks = [(s, s + d) for n, s, d in record["host_spans"] if n == WINDOW_SPAN]
    if not marks:
        raise ValueError("the trace holds no window span")
    w0, w1 = marks[0]
    seconds: dict[str, float] = defaultdict(float)
    unmapped = 0.0
    devices = record["devices"]
    for dev, ops in devices.items():
        starts, ends = module_runs(module, record["modules"].get(dev, ()))
        inside = []
        for n, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a and _inside(s, starts, ends):
                inside.append((op_name(n), a, b - a))
        for name, t in self_times(inside).items():
            cls = scopes.get(name)
            if cls is None:
                unmapped += t * 1e-9
                cls = "other"
            seconds[cls] += t * 1e-9
    n = max(len(devices), 1)
    out = {cls: seconds.get(cls, 0.0) / n for cls in CLASSES}
    out["unmapped"] = unmapped / n
    return out
