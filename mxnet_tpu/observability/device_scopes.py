"""Device time by scope: which graph node, and which pass over it, each
instruction of a compiled train step came from.

The host side of a step has been named by :mod:`.spans` since PR 25; this
is its counterpart for the device.  Three pieces, one mechanism:

- **Scopes**, where the work is lowered.  ``executor._run_node`` runs
  every graph node's ``forward`` under ``jax.named_scope(node.name)``;
  ``train_step`` opens ``update`` around the optimizer's update and the
  trainer ``grad_sync`` around its gradient buckets; a few ops open the
  sub-scopes :data:`.phases.DEVICE_SUBSCOPES` inside their node.  jax's
  own name stack adds the pass: an instruction's ``op_name`` reads
  ``…/jvp(<node>)/…`` in the forward pass, ``…/transpose(…)/…<node>…``
  in the backward and ``…/rematted_computation/<node>/…`` where a
  mirrored segment recomputes it.  :func:`classify` is the one reading
  of that grammar.  A scope is metadata: it changes no fusion.
- **The map**, kept by the program.  At a step's first dispatch its
  owner (``Executor``, ``ShardedTrainer``) calls :func:`register` with
  the jitted step and its abstract arguments; the :class:`StepRecord`
  holds no device buffer and outlives its owner.  ``record.scopes()``
  lowers and compiles on first use and keeps ``{instruction name:
  op_name}`` (:func:`parse`), not the text.
- **The join**, by whoever holds a profiler capture
  (``perfbench/readers/device_scope.py``, ``tools/device_scopes.py``):
  :func:`inside` keeps the device events of the step's own module,
  ``perfbench.trace_reduce.self_times`` reduces them (a ``while`` is
  charged only what its body does not cover) and :func:`table` charges
  each instruction's time to its node's op type and phase.

Nothing here runs per step, reads the environment or imports jax at
import time.  docs/observability.md, "Device time by scope".
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import re

from .phases import (COMPILER_NAMED, DEVICE_PHASES, DEVICE_SUBSCOPES,
                     GRAD_SYNC, UPDATE)

__all__ = ["parse", "classify", "StepRecord", "register", "records",
           "latest", "abstractify", "graph_nodes", "inside", "table",
           "lines", "dump", "load"]

FORWARD, RECOMPUTE, BACKWARD, _, _, OTHER = DEVICE_PHASES
#: jax's name for the second run of a checkpointed function
REMATTED = "rematted_computation"

_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
# ``%body (p: f32[2]) -> f32[2] {`` as a compiled module prints it,
# ``body {`` as an unoptimized one does
_COMPUTATION = re.compile(
    r"^(?!HloModule)(?:ENTRY\s+)?%?([^\s({]+)\s*(?:\(.*)?\{\s*$")
_NAMES = re.compile(r"%([^\s,(){}=]+)")
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")
#: its default: the compiled text keeps its metadata
_OWN_COMPILE = {"xla_dump_disable_metadata": False}


def parse(hlo_text, held=None):
    """``{instruction name: op_name}`` of an HLO module's text
    (``compiled.as_text()``), ``""`` where an instruction carries none.

    Every computation but a fusion's body (what a ``calls=`` names): a
    fusion is one device event under its own name and carries its
    root's ``op_name``; a ``while`` body's instructions are events of
    their own.  An instruction whose ``op_name`` holds no scope at all
    (no ``/``: the compiler made it, as layout assignment makes a
    ``copy`` and XLA:TPU makes ``ragged-dot-none`` of
    ``lax.ragged_dot``) is put under the scope of the instruction that
    uses it — the pass that needs the copy pays for it — and, where no
    user within a few steps has one, under what its computation's
    scoped instructions share (a routed layer's chunk loop is one node
    in one pass); :data:`.phases.COMPILER_NAMED` gives its sub-scope.
    Into ``held``, where a dict is given, goes ``{fusion: the op_names
    inside its body}`` (through fusions nested in it): what else a
    fusion holds beside its root."""
    computations = {}
    users = {}
    body_of = {}
    arguments = set()
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), {})
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        for operand in _NAMES.findall(line, m.end()):
            if operand in current:
                users.setdefault(operand, []).append(name)
        found = _OP_NAME.search(line)
        current[name] = found.group(1) if found else ""
        if " parameter(" in line:       # named after the argument it is
            arguments.add(name)
        for body in _CALLS.findall(line):
            body_of[name] = body
    fused = set(body_of.values())

    def inside(body):
        names = set()
        for instruction, op_name in computations.get(body, {}).items():
            names.add(op_name)
            if instruction in body_of:
                names |= inside(body_of[instruction])
        return names

    out = {}
    for name, instructions in computations.items():
        if name in fused:
            continue
        shared = _shared_scope(instructions.values())

        def users_scope(instruction, hops=4):
            for user in users.get(instruction, ()) if hops else ():
                op_name = instructions.get(user, "")
                found = "/".join(_components(op_name)[:-1]) \
                    if "/" in op_name else users_scope(user, hops - 1)
                if found:
                    return found
            return ""

        placed = {}
        for instruction, op_name in instructions.items():
            scope = "" if "/" in op_name or instruction in arguments \
                else users_scope(instruction) or shared
            if scope:
                made = op_name or instruction
                sub = next((s for stem, s in COMPILER_NAMED.items()
                            if made.startswith(stem)
                            and not scope.endswith("/" + s)), None)
                placed[instruction] = "/".join(
                    [scope] + ([sub] if sub else []) + [made])
        out.update(instructions)
        out.update(placed)
        if held is not None:
            held.update((i, inside(body_of[i])) for i in instructions
                        if i in body_of)
    return out


def _shared_scope(op_names):
    """The scope components every scoped ``op_name`` begins with, as a
    path; ``""`` where they share none."""
    scoped = [_components(n)[:-1] for n in op_names if "/" in n]
    return "/".join(os.path.commonprefix(scoped)) if scoped else ""


def _components(op_name):
    """``op_name`` split at the slashes outside parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


def _bare(component):
    """``transpose(jvp(layer0_att))`` -> ``layer0_att``."""
    while True:
        m = _WRAPPED.match(component)
        if not m:
            return component
        component = m.group(1)


def classify(op_name, nodes):
    """``(phase, node, sub)`` of one instruction's ``op_name``.

    ``nodes`` holds the graph's node names (``{name: op type}``).
    ``phase`` is one of :data:`.phases.DEVICE_PHASES`; ``node`` the
    innermost component that is a graph node, ``sub`` the innermost of
    :data:`.phases.DEVICE_SUBSCOPES` inside it; both ``None`` outside a
    node.  The last component is the primitive's own name, never a
    scope."""
    scopes = _components(op_name)[:-1]
    if UPDATE in scopes:
        return UPDATE, None, None
    if GRAD_SYNC in scopes:
        return GRAD_SYNC, None, None
    bare = [_bare(c) for c in scopes]
    at = None
    for i, name in enumerate(bare):
        if name in nodes:
            at = i
    if at is None:
        return OTHER, None, None
    sub = next((s for s in reversed(bare[at + 1:])
                if s in DEVICE_SUBSCOPES), None)
    if REMATTED in scopes[:at]:
        phase = RECOMPUTE
    elif any(c.startswith("transpose(") for c in scopes):
        phase = BACKWARD
    else:
        phase = FORWARD
    return phase, bare[at], sub


def abstractify(a):
    """ShapeDtypeStruct (with sharding when present) for jit.lower().

    Single-device shardings (the uncommitted rng key) are dropped:
    baking them in would make lower() reject the mix with mesh-sharded
    arguments that the real dispatch accepts.  Host scalars have none."""
    import jax
    from jax.sharding import SingleDeviceSharding
    sh = getattr(a, "sharding", None)
    if sh is not None and not isinstance(sh, SingleDeviceSharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def graph_nodes(symbol):
    """``{node name: op type}`` over a Symbol's operator nodes."""
    return {n.name: n.op.op_name or type(n.op).__name__
            for n in symbol._topo() if not n.is_variable}


class StepRecord(object):
    """One compiled step: its module's name as a trace's ``XLA Modules``
    line prints it, the jitted function with the abstract arguments of
    its dispatch, the graph's ``{node: op type}``, and — once asked for
    — ``{instruction: op_name}`` of the compiled module.  No device
    buffer is held: the record may outlive the executor or trainer that
    registered it."""

    def __init__(self, module, jitted=None, args=(), nodes=None,
                 context=None, scopes=None, held=None):
        self.module = module
        self.jitted = jitted
        self.args = args
        self.nodes = dict(nodes or {})
        self.context = context or contextlib.nullcontext
        self._scopes = scopes
        self._held = held
        self._classified = None
        self._memo = {}

    def lower(self):
        """The step lowered at the shapes and shardings of its dispatch
        (``context``: what the owner holds open while it traces)."""
        with self.context():
            return self.jitted.lower(*self.args)

    def compiled_text(self):
        """The optimized HLO text of the step, from a compile of its
        own — not the running executable's text: jax keys its compile
        cache on the module *without* its metadata, so the executable a
        step runs may be one that an earlier tree compiled, with that
        tree's scopes in its text (and ``lowered.compile()`` hands the
        running one back from memory).  This compile takes the metadata
        into the key, so the cache answers it only with a module of
        these very scopes; the option it passes asks for nothing and
        makes the request a different one from the dispatch's.  An
        event's name is its opcode and the module-wide number of its
        instruction (``fusion.12``), which metadata does not move: the
        names are the running executable's."""
        import jax
        key = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            return self.lower().compile(
                compiler_options=_OWN_COMPILE).as_text()
        finally:
            jax.config.update(key, was)

    def scopes(self):
        """``{instruction name: op_name}`` of the compiled step: the
        first call lowers and compiles (:meth:`compiled_text`); the map
        and :meth:`held` are kept and the text is not."""
        if self._scopes is None:
            held = {}
            self._scopes = parse(self.compiled_text(), held)
            self._held = {
                fusion: sorted({self._classify(op)[:2]
                                for op in op_names if op},
                               key=lambda pn: (pn[0], pn[1] or ""))
                for fusion, op_names in held.items()}
        return self._scopes

    def held(self):
        """``{fusion: [(phase, node)]}``: every scope with an instruction
        inside the fusion's body.  A fusion is charged to its root's
        scope alone; this says whose work it holds beside."""
        self.scopes()
        return self._held or {}

    def _classify(self, op_name):
        if op_name not in self._memo:
            self._memo[op_name] = classify(op_name, self.nodes)
        return self._memo[op_name]

    def classified(self):
        """``{instruction name: (phase, node, sub)}`` (kept)."""
        if self._classified is None:
            self._classified = {
                name: self._classify(op_name)
                for name, op_name in self.scopes().items()}
        return self._classified


#: the steps this process dispatched, newest last; bounded, because a
#: record keeps its jitted function (and so its executables) alive
_RECORDS = collections.deque(maxlen=16)


def register(module, jitted, args, nodes, context=None):
    """Called by a step's owner at the step's first dispatch."""
    import jax
    record = StepRecord(module, jitted,
                        jax.tree_util.tree_map(abstractify, args), nodes,
                        context)
    _RECORDS.append(record)
    return record


def records():
    """The registered steps, newest last."""
    return list(_RECORDS)


def latest(modules=None):
    """The newest record — of those whose module is in ``modules``,
    where given — or ``None``."""
    for record in reversed(_RECORDS):
        if modules is None or record.module in modules:
            return record
    return None


def module_of(event_name):
    """``jit_train_step(1234567)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0]


def inside(events, module_events, module):
    """The ``(name, start_ns, dur_ns)`` device events that begin inside
    an execution of ``module`` (``module_events``: a chip's ``XLA
    Modules`` line).  Another program's ``fusion.3`` is not the step's."""
    spans = sorted((s, s + d) for n, s, d in module_events
                   if module_of(n) == module)
    out = []
    i = 0
    for ev in sorted(events, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= ev[1]:
            i += 1
        if i == len(spans):
            break
        if spans[i][0] <= ev[1]:
            out.append(ev)
    return out


def table(self_ns, record):
    """Device time by scope.  ``self_ns`` is ``{instruction name: ns}``,
    already reduced so that an enclosing ``while`` is charged only what
    its body does not cover (``perfbench.trace_reduce.self_times`` over
    the events :func:`inside` kept).  Returns::

        {"total_ns": all of it,
         "joined_ns": what the record's map names at all,
         "scoped_ns": what lies under a node, ``update`` or ``grad_sync``,
         "by_phase": {phase: ns},
         "by_type_phase": {(op type, phase): ns},   # nodes only
         "by_node": {node: ns},
         "by_sub": {(op type, sub): ns},
         "held": {op type or phase: ns},   # below
         "unscoped": {instruction name: ns}}        # phase other, and
                                                    # names not in the map

    A fusion is charged whole to its root's scope.  ``held`` is the
    time of the fusions charged elsewhere that hold an instruction of
    ``update`` / ``grad_sync``, or of a node of that op type: with the
    type's own time, an upper bound on what it costs.
    """
    classified = record.classified()
    held = record.held()
    out = {"total_ns": 0.0, "joined_ns": 0.0, "by_phase": {},
           "by_type_phase": {}, "by_node": {}, "by_sub": {}, "held": {},
           "unscoped": {}}

    def add(where, key, ns):
        out[where][key] = out[where].get(key, 0.0) + ns

    for name, ns in self_ns.items():
        out["total_ns"] += ns
        found = classified.get(name)
        if found is None:
            add("unscoped", name, ns)
            continue
        out["joined_ns"] += ns
        phase, node, sub = found
        add("by_phase", phase, ns)
        op_type = record.nodes[node] if node is not None else phase
        for label in {record.nodes[n] if n is not None else p
                      for p, n in held.get(name, ())} - {op_type, OTHER}:
            add("held", label, ns)
        if node is None:
            if phase == OTHER:
                add("unscoped", name, ns)
            continue
        add("by_type_phase", (op_type, phase), ns)
        add("by_node", node, ns)
        if sub is not None:
            add("by_sub", (op_type, sub), ns)
    out["scoped_ns"] = sum(ns for phase, ns in out["by_phase"].items()
                           if phase != OTHER)
    return out


def lines(tab, steps=1, n_types=12, n_nodes=10):
    """The table as text, milliseconds a step: one format for the
    benchmark's log line and ``tools/device_scopes.py``."""
    per = 1e-6 / max(1, steps)

    def largest(totals, n):
        return sorted(totals.items(), key=lambda kv: -kv[1])[:n]

    total = tab["total_ns"] or 1.0
    out = ["device %.2f ms a step, scoped %.1f %%: %s" % (
        tab["total_ns"] * per, 100.0 * tab["scoped_ns"] / total,
        ", ".join("%s %.2f" % (p, tab["by_phase"][p] * per)
                  for p in DEVICE_PHASES if p in tab["by_phase"]))]
    out.append("by op type and phase: " + ", ".join(
        "%s %s %.2f" % (t, p, ns * per)
        for (t, p), ns in largest(tab["by_type_phase"], n_types)))
    out.append("by node: " + ", ".join(
        "%s %.2f" % (node, ns * per)
        for node, ns in largest(tab["by_node"], n_nodes)))
    if tab["by_sub"]:
        out.append("by sub-scope: " + ", ".join(
            "%s %s %.2f" % (t, s, ns * per)
            for (t, s), ns in largest(tab["by_sub"], n_types)))
    if tab["held"]:
        out.append("held in fusions charged elsewhere: " + ", ".join(
            "%s %.2f" % (label, ns * per)
            for label, ns in largest(tab["held"], n_types)))
    out.append("unscoped: " + ", ".join(
        "%s %.2f" % (name, ns * per)
        for name, ns in largest(tab["unscoped"], n_nodes)))
    return out


def dump(path, record=None):
    """Write a record (the newest by default) as JSON for
    ``tools/device_scopes.py --scopes``; compiles if it has not yet."""
    record = record or latest()
    if record is None:
        raise ValueError("device_scopes: no step was registered")
    with open(path, "w") as f:
        json.dump({"module": record.module, "nodes": record.nodes,
                   "scopes": record.scopes(), "held": record.held()}, f)


def load(path):
    """The record :func:`dump` wrote (it cannot lower again)."""
    with open(path) as f:
        d = json.load(f)
    return StepRecord(d["module"], nodes=d["nodes"], scopes=d["scopes"],
                      held={f: [tuple(pn) for pn in found]
                            for f, found in d.get("held", {}).items()})
