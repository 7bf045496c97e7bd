"""Reduce a profiler trace to device time per engine stage.

The program names its stages with `jax.named_scope` (core/network.py):
``engine.inbox``, ``protocol.step``, ``engine.route`` and
``engine.bin``.  A protocol names the parts of its step with dotted
scopes of its own, reported under the stage as sub-scopes: Handel's
``handel.receive``, ``handel.apply``, ``handel.verify`` and
``handel.disseminate``.  A scope lands in each op's ``op_name``
metadata (a fusion's is its root's), e.g. ``jit(chunk_batched)/while/
body/closed_call/protocol.step/vmap(handel.verify)/gather``.  Time of
the chunk program
(modules ``jit_chunk_*``) under no stage scope is the fifth stage,
``engine.carry``: loop control and the copies XLA inserts for the scan
carry, which no scope can name because XLA adds them after tracing.
Ops of the other programs in the window (the seed init, the check's
reduction) are reported under their module's name and go into no
stage.

On the TPU an event of a device's ``XLA Ops`` line carries the op's HLO
text but not its metadata, and the ``XLA Modules`` line says which
program ran when.  So an op's ``op_name`` is looked up by its HLO name
in the compiled module's text (`op_names`).  Per chip, each stage's
seconds are the union of its innermost op intervals clipped to
``bench.window``, as `trace.reduce` takes busy time.  Where the chunk
program's ops carry no stage scope at all (an executable compiled
before the scopes existed and loaded from the persistent cache, whose
key ignores metadata) the stages are None, never "all carry".

The stream kind (`kinds/stream.py`) reduces a ``--trace 1`` run's trace
here before deleting it: the stages' ms per seed-sim-ms, the sub-scopes
and ``breakdown.device_stages`` go into its record, where the
``engine.*_ms_per_simms`` and ``protocol.step_ms_per_simms`` readers
find them.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from . import trace as trace_mod

#: the engine's stage scopes, in program order, then what no scope holds
STAGES = ("engine.inbox", "protocol.step", "engine.route", "engine.bin")
CARRY = "engine.carry"
#: module-name prefix of the chunk programs (serve/registry.py names them)
CHUNK_MODULE = "jit_chunk_"
MODULES_LINE = "XLA Modules"


# ------------------------------------------------------------- names

def scopes(op_name: str) -> list:
    """The components of a scope path, transform wrappers taken off
    (``vmap(handel.verify)`` -> ``handel.verify``)."""
    out = []
    for c in op_name.split("/"):
        while (m := re.fullmatch(r"[\w.-]+\((.*)\)", c)):
            c = m[1]
        out.append(c)
    return out


def stage_of(op_name: str | None):
    """``(stage, sub)`` of a chunk-program op: the first stage scope on
    its path (`CARRY` if none) and, under it, the first sub-scope: a
    dotted scope (``handel.verify``, ``pingpong.reply``) before the op's
    own name, which ends the path.  Where XLA merged ops it joins their
    paths with ``;``; the first one counts."""
    path = scopes((op_name or "").split(";")[0])
    for i, c in enumerate(path):
        if c in STAGES:
            sub = next((s for s in path[i + 1:-1]
                        if "." in s and s not in STAGES), None)
            return c, sub
    return CARRY, None


def module_name(event_name: str) -> str:
    """``jit_chunk_batched(1221...)`` -> ``jit_chunk_batched``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_of(event_name: str) -> str:
    """An op event's HLO instruction name (``%fusion.12 = ...`` ->
    ``fusion.12``)."""
    m = re.match(r"%?([^\s=]+) = ", event_name)
    return m[1] if m else event_name


_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def op_names(hlo_text: str) -> dict:
    """Instruction name -> scope path (``op_name``) in a compiled
    module's text, None where it has none.  An instruction without one
    (XLA drops it from some scatters it rewrites) takes its called
    computation's (``calls=`` / ``to_apply=``): its root's, else the
    one most of its instructions carry."""
    own, calls, roots, inside, comp = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        if (head := _HEAD.match(line)):
            comp = head[1]
            continue
        if not (m := _INSTR.match(line)):
            continue
        op = re.search(r'op_name="([^"]+)"', line)
        own[m[2]] = op[1] if op and "/" in op[1] else None
        if own[m[2]]:
            inside.setdefault(comp, []).append(own[m[2]])
        if (called := re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", line)):
            calls[m[2]] = called[1]
        if m[1]:
            roots[comp] = m[2]
    def resolve(name, seen=()):
        if own[name] or name not in calls or name in seen:
            return own[name]
        root = roots.get(calls[name])
        ops = inside.get(calls[name], [])
        return ((resolve(root, seen + (name,)) if root in own else None)
                or (max(ops, key=ops.count) if ops else None))

    return {name: resolve(name) for name in own}


# ------------------------------------------------------------- reduce

def reduce(devices, host_spans, names=None, seed_sim_ms=None):
    """`devices`: per chip, ``(ops, modules)``: its ops line and its
    modules line as ``(name, start_ns, end_ns)``; `host_spans` as
    `trace.reduce` takes them; `names`: module -> `op_names` of it.

    Returns seconds averaged over the chips: ``stages`` (stage -> s, or
    None where the chunk program's ops carry no stage scope), ``sub``
    (``protocol.step/handel.x`` -> s), ``modules`` (another program's
    module -> s), ``busy_s`` (the union of them all),
    ``unnamed_ops`` (chunk-program ops missing from `names`: a text of
    another executable) and ``carry_ops`` (the carry's top ops), plus
    ``ms_per_simms`` per stage when `seed_sim_ms` is given.  None
    without a window or a device."""
    win = [s for s in host_spans if s[0] == trace_mod.WINDOW]
    if not win or not devices:
        return None
    w0, w1 = win[0][1], win[0][2]
    names = names or {}
    acc, carry_ops, busy, scoped, unnamed = {}, {}, 0, False, 0
    for ops, modules in devices:
        mods = sorted((s, e, module_name(n)) for n, s, e in modules)
        starts = [m[0] for m in mods]
        keyed = {}
        for n, s, e in trace_mod.innermost(ops):
            if e <= w0 or s >= w1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] \
                else "unattributed"
            if module.startswith(CHUNK_MODULE):
                known = names.get(module, {})
                unnamed += op_of(n) not in known
                stage, sub = stage_of(known.get(op_of(n)))
                scoped = scoped or stage != CARRY
                if stage == CARRY:      # with its op_name, if any
                    op = (trace_mod._short(n), known.get(op_of(n)))
                    carry_ops[op] = carry_ops.get(op, 0) + (
                        min(e, w1) - max(s, w0))
                keys = [stage] + ([f"{stage}/{sub}"] if sub else [])
            else:
                keys = [module]
            for key in keys:
                keyed.setdefault(key, []).append((max(s, w0), min(e, w1)))
        busy += sum(b - a for a, b in trace_mod.union(
            iv for key, ivs in keyed.items() if "/" not in key
            for iv in ivs))
        for key, ivs in keyed.items():
            acc[key] = acc.get(key, 0) + sum(
                b - a for a, b in trace_mod.union(ivs))
    k = len(devices)
    sec = {key: v / k / 1e9 for key, v in acc.items()}
    own = STAGES + (CARRY,)
    out = {"stages": {s: sec.get(s, 0.0) for s in own} if scoped else None,
           "sub": {key: v for key, v in sec.items()
                   if "/" in key and scoped},
           "modules": {key: v for key, v in sec.items()
                       if key not in own and "/" not in key},
           "busy_s": busy / k / 1e9,
           "unnamed_ops": unnamed / k,
           "carry_ops": [[n, op, t / k / 1e9] for (n, op), t in sorted(
               carry_ops.items(), key=lambda kv: -kv[1])[:trace_mod.TOP]]}
    if seed_sim_ms:
        out["ms_per_simms"] = ({s: 1000.0 * v / seed_sim_ms
                                for s, v in out["stages"].items()}
                               if scoped else None)
    return out


def device_stages(red) -> list:
    """The breakdown's ``device_stages``: ``[stage or module, seconds]``
    pairs, each stage followed by its sub-scopes, then the modules."""
    if red is None:
        return []
    rows = []
    for s, v in (red["stages"] or {}).items():
        rows.append([s, v])
        rows += [[k, t] for k, t in sorted(red["sub"].items(),
                                           key=lambda kv: -kv[1])
                 if k.startswith(s + "/")]
    return rows + [[m, t] for m, t in sorted(red["modules"].items(),
                                             key=lambda kv: -kv[1])]


def reduce_dir(trace_dir, names=None, seed_sim_ms=None):
    """`reduce` over the xplane file under `trace_dir`: the ops and
    modules lines of each accelerator plane, and the ``bench.*`` host
    annotations."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (trace_mod.OPS_LINE, MODULES_LINE)}
            if trace_mod.OPS_LINE in lines:
                devices.append((lines[trace_mod.OPS_LINE],
                                lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return reduce(devices, host, names, seed_sim_ms)


# ------------------------------------------------------------- a run

def chunk_op_names(program, state) -> dict:
    """`op_names` of a chunk program (a registry's `chunk_fn`), compiled
    for `state` (shapes, e.g. `jax.eval_shape` of the init): in a
    process that ran it, a load from the persistent cache, the
    executable the trace saw."""
    fn = program.__wrapped__
    return {f"jit_{fn.__name__}":
            op_names(fn.lower(*state).compile().as_text())}
