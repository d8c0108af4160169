"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``jax.profiler.ProfileData`` reads the file.  A TPU's plane is named
``/device:TPU:<id>``; its ``XLA Ops`` line holds one event per operation
that ran on the chip, named by the operation's HLO text (which states its
shapes), and its ``XLA Modules`` line one event per program, named
``<module>(<fingerprint>)``.
The host's plane holds the harness's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``).

What comes out, with every time in seconds:

* ``busy_s``: the union of the op intervals of each chip, averaged over
  the chips;
* ``ops``: device time per operation, as ``<program>:<op> <shape>``,
  summed over the chips;
* ``modules``: device time and calls per program, under the program's
  stable name (the module name without its numeric suffix);
* ``kernels``: for each Pallas kernel, by the name of its kernel
  function, one entry per call with its device time and the shapes of
  its operands as the HLO states them;
* ``breakdown``: the ten operations that took most device time, and
  the ten longest idle gaps of the first chip, each named by the
  harness span that was open on the host at its middle.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'

_SHAPE = re.compile(r"(f32|bf16|s32|u32|f16|s8|u8|pred)\[([0-9,]*)\]")
_SUFFIX = re.compile(r"\(\d+\)$|\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"%([A-Za-z_][\w-]*?)(?:\.\d+)* = \(?(\S+)")


def shapes(text: str) -> list:
    """Every ``dtype[d0,d1,...]`` in an HLO text, as tuples of ints."""
    return [tuple(int(x) for x in dims.split(",") if x)
            for _, dims in _SHAPE.findall(text or "")]


def _no_layouts(hlo: str) -> str:
    """An HLO text without its layouts (``{1,0:T(8,128)S(1)}``), whose
    parentheses would end an operand list early."""
    return _LAYOUT.sub("", hlo)


def operand_shapes(hlo: str) -> list:
    """Shapes of a custom call's operands: those inside its
    ``custom-call(...)`` (the ones before it are its results)."""
    hlo = _no_layouts(hlo)
    at = hlo.find("custom-call(")
    if at < 0:
        return []
    end = hlo.find(")", at)
    return shapes(hlo[at:end])


def op_name(hlo: str) -> str:
    """A short, stable name of an op event: its HLO instruction's name
    without the numeric suffix, and its first result shape."""
    m = _INSTR.match(_no_layouts(hlo))
    if not m:
        return hlo[:80]
    shape = _SHAPE.match(m.group(2))
    return m.group(1) + (f" {shape.group(0)}" if shape else "")


def kernel_calls(trace, fragment: str) -> list:
    """Every call of the Pallas kernels whose name holds ``fragment``."""
    if trace is None:
        return []
    return [call for name, calls in trace["kernels"].items()
            if fragment in name for call in calls]


def module_name(name: str) -> str:
    """``jit__ingest(42)`` -> ``jit__ingest``."""
    return _SUFFIX.sub("", name)


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def kernel_of(name: str):
    """The Pallas kernel an op event ran, or None.  A compiled Pallas
    kernel is a ``tpu_custom_call`` whose HLO instruction is named after
    the kernel's jitted entry point (``%kmeans_assign_pallas.8 = ...``);
    the name without its numeric suffix is the stable one."""
    if PALLAS_TARGET not in name:
        return None
    m = re.match(r"%([A-Za-z_][\w]*?)(\.\d+)? =", name)
    return m.group(1) if m else None


def reduce(path: str, device_ids=None) -> dict:
    """The numbers of one trace, over the chips whose ids (as strings)
    are in ``device_ids`` (every TPU of the trace when None)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)
               and (device_ids is None
                    or p.name[len(DEVICE_PREFIX):] in device_ids)]
    ops = defaultdict(float)
    modules = defaultdict(lambda: {"s": 0.0, "n": 0})
    kernels = defaultdict(list)
    busy = []
    first_busy = None
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       module_name(ev.name))
                      for ev in lines.get(MODULES_LINE, []))
        for a, b, name in mods:
            modules[name]["s"] += (b - a) * 1e-9
            modules[name]["n"] += 1
        starts = [m[0] for m in mods]
        intervals = []
        for ev in lines.get(OPS_LINE, []):
            dur = ev.duration_ns * 1e-9
            at = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = mods[at][2] if at >= 0 and ev.start_ns < mods[at][1] \
                else "?"
            ops[f"{mod}:{op_name(ev.name)}"] += dur
            intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            kernel = kernel_of(ev.name)
            if kernel is not None:
                kernels[kernel].append(
                    {"s": dur, "operands": operand_shapes(ev.name)})
        merged = union(intervals)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for plane in planes if not plane.name.startswith(DEVICE_PREFIX)
             for line in plane.lines for ev in line.events
             if ev.name.startswith(HOST_SPAN_PREFIX)]
    merged = first_busy or []
    gaps = sorted((((b - a) * 1e-9, a, b) for (_, a), (b, _)
                   in zip(merged, merged[1:])), reverse=True)[:10]
    top_gaps = []
    for length, a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = [n for s, e, n in spans if s <= mid <= e]
        top_gaps.append((open_[-1] if open_ else "none", length))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "ops": dict(ops),
        "modules": dict(modules),
        "kernels": dict(kernels),
        "breakdown": {"device_ops": [[n, s] for n, s in top_ops],
                      "idle_gaps": [[n, s] for n, s in top_gaps]},
    }
