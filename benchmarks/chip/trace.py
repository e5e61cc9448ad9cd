"""From a profiler trace to the numbers the per-layer metrics read.

`load_events` reads the `.xplane.pb` that `jax.profiler` writes, with
JAX's own `ProfileData`, into plain tuples: the device's operations (the
"XLA Ops" line of each TPU plane) and the host's `TraceAnnotation`
spans whose names start with `bench.` (the harness's own).  Everything
after that works on tuples, so the arithmetic is tested without a chip.

    busy_ns        length of the union of intervals
    classify_hlo   HLO instruction name -> "conv" | "routing_kernel" |
                   "other", read off a compiled program's HLO text
    kernel_names   HLO instruction name -> kernel name, for every Pallas
                   (Mosaic) kernel of a compiled program
    reduce         busy and idle time of the traced window, device time
                   per class and per Pallas kernel, the operations that
                   took most time, and the longest idle gaps named by
                   the host span that covers them
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load_events(trace_dir: str) -> dict:
    """{"device": {plane: [(name, start_ns, dur_ns)]}, "host": [...]}."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {paths}")
    pd = ProfileData.from_file(paths[0])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                          for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"device": device, "host": host}


def merge(intervals) -> list:
    """Sorted, disjoint [start, end) intervals covering the input."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals) -> float:
    return float(sum(e - s for s, e in merge(intervals)))


def clip(events, start: float, end: float) -> list:
    """Events cut to [start, end); those wholly outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, start), min(s + d, end)
        if b > a:
            out.append((name, a, b - a))
    return out


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s"
                    r"(fusion|custom-call|convolution)\(")
_EVENT = re.compile(r"^\s*%?([\w.\-]+)")


def instr_name(event_name: str) -> str:
    """The HLO instruction an "XLA Ops" event ran.  On the TPU the event
    is named by the instruction's whole text ("%fusion.2 = s8[...]
    fusion(...), kind=..."), elsewhere by its bare name."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMP = re.compile(r"^%?([\w.\-]+)\s.*\{\s*$")


def classify_hlo(hlo_text: str) -> dict:
    """Instruction name -> class, for every instruction of the compiled
    module that is a convolution of the conv stack, a fusion that holds
    one, or a Mosaic kernel.  The routing kernel is the `tpu_custom_call`
    whose name holds "routing"; other kernels and ops are "other"."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)

    def is_conv(line):
        # XLA lowers some dots (u_hat's einsum) to convolutions too; the
        # conv stack is what the program wrote as conv_general_dilated
        return " convolution(" in line and "conv_general_dilated" in line

    def has_conv(comp, seen=()):
        for line in comps.get(comp, ()):
            if is_conv(line):
                return True
            m = _CALLS.search(line)
            if m and m.group(1) not in seen and \
                    has_conv(m.group(1), seen + (comp,)):
                return True
        return False

    classes = {}
    for comp, lines in comps.items():
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            name, kind = m.groups()
            if kind == "custom-call":
                if "tpu_custom_call" in line:
                    classes[name] = "routing_kernel" if "routing" in name \
                        else "other"
            elif kind == "convolution":
                if is_conv(line):
                    classes[name] = "conv"
            else:
                c = _CALLS.search(line)
                if c and has_conv(c.group(1)):
                    classes[name] = "conv"
    return classes


_SUFFIX = re.compile(r"\.\d+$")


def kernel_names(hlo_text: str) -> dict:
    """Instruction name -> kernel name for every `tpu_custom_call` of the
    compiled module.  The custom call is named after its kernel, with
    XLA's `.N` suffix: `routing_q7_pallas.1` -> `routing_q7_pallas`."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) == "custom-call" and "tpu_custom_call" in line:
            out[m.group(1)] = _SUFFIX.sub("", m.group(1))
    return out


def reduce(events: dict, window: tuple, classes: dict, kernels: dict,
           top: int = 10) -> dict:
    """Reduce the traced events inside `window` = (start_ns, end_ns);
    `classes` from `classify_hlo`, `kernels` from `kernel_names`.

    Busy time is the union of the device's operation intervals, averaged
    over the devices that ran any.  An idle gap is named by the host span
    that covers most of it (a gap no host span covers is "untraced")."""
    start, end = window
    per_device = {p: clip(evs, start, end)
                  for p, evs in events["device"].items()}
    per_device = {p: evs for p, evs in per_device.items() if evs}
    window_ns = float(end - start)
    busy = class_ns = None
    op_ns: dict = collections.Counter()
    kernel_ns: dict = collections.Counter()
    gaps: list = []
    if per_device:
        busies = []
        class_ns = collections.Counter()
        for evs in per_device.values():
            busies.append(busy_ns((s, s + d) for _, s, d in evs))
            for name, s, d in evs:
                op_ns[name] += d
                instr = instr_name(name)
                class_ns[classes.get(instr, "other")] += d
                if instr in kernels:
                    kernel_ns[kernels[instr]] += d
            merged = merge((s, s + d) for _, s, d in evs)
            edges = [start] + [x for iv in merged for x in iv] + [end]
            gaps += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
        busy = sum(busies) / len(busies)
        class_ns = {k: v / len(per_device) for k, v in class_ns.items()}
    host = clip(events["host"], start, end)
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = [(_cover(host, a, b), (b - a) / 1e9) for a, b in gaps[:top]]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": None if busy is None else busy / 1e9,
        "class_s": {k: v / 1e9 for k, v in (class_ns or {}).items()},
        "kernel_s": {k: v / len(per_device) / 1e9
                     for k, v in kernel_ns.items()},
        "device_ops": [[n, t / 1e9] for n, t in op_ns.most_common(top)],
        "idle_gaps": named_gaps,
        "devices": len(per_device),
    }


def _cover(host, a: float, b: float) -> str:
    """The host span that overlaps [a, b) the most; an inner span wins
    over the enclosing `bench.window` (the harness loop itself), and of
    equal overlaps the shortest wins."""
    best, key = "untraced", (False, 0.0, 0.0)
    for name, s, d in host:
        ov = min(s + d, b) - max(s, a)
        k = (name != WINDOW_SPAN, ov, -d)
        if ov > 0 and k > key:
            best, key = name, k
    return best
