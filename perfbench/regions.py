"""Device time by model region: the compiled step's named scopes joined with
the profiler trace.

The program wraps each region of its train step in ``jax.named_scope``, with
the vocabulary in ``REGIONS``; this copy is the contract, so a program change
that renames a scope fails a test here instead of silently moving a metric.
The TPU trace names each ``XLA Ops`` event by its HLO text without metadata
(``%fusion.670 = bf16[...] fusion(...), kind=kOutput, ...``), so an op's
region comes from the compiled step's HLO text (``compiled.as_text()``), where
an instruction carries ``metadata={op_name="jit(train_step)/..."}``:

- region: the deepest path segment (or pair of segments, ``attn/core``) that
  names a region, once transform wrappers such as ``jvp(...)`` and
  ``transpose(...)`` are stripped; ``other`` if none does;
- direction: ``remat`` if the path holds ``rematted_computation`` (the
  forward recomputed inside the backward), else ``bwd`` if it holds
  ``transpose(``, else ``fwd``.

A fusion whose own instruction carries no metadata takes it from the
instructions of the computation it calls, its root first.

``offload`` is the one region that no scope names: XLA's host offloading
rewrites the step's ``device_put``s between pinned host memory and HBM into
copies without ``op_name``, so the map assigns it by the copies' memory space
(``region_map``).
"""
from __future__ import annotations

import dataclasses
import re

from perfbench import trace_reduce as tr

REGIONS = ("embed", "layers", "norm", "attn/qkv", "attn/core", "attn/out",
           "mlp", "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
           "head", "grad_norm", "optimizer", "offload")
OTHER = "other"
DIRECTIONS = ("fwd", "remat", "bwd")
STEP_MODULE = "jit_train_step"

_WRAPPER = re.compile(r"[A-Za-z_][\w.-]*\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COPY_DONE = re.compile(r" copy-done\(.*?%?([\w.\-]+)\)")


def _one_region(path: str) -> str:
    segs = _WRAPPER.sub("", path).replace(")", "").split("/")
    region, i = OTHER, 0
    while i < len(segs):
        pair = "/".join(segs[i:i + 2])
        if pair in REGIONS:
            region, i = pair, i + 2
            continue
        if segs[i] in REGIONS:
            region = segs[i]
        i += 1
    return region


def region_of(op_name: str) -> tuple:
    """(region, direction) of one ``op_name``. XLA joins the names of ops it
    merged with ``;``: the first that names a region decides."""
    for path in op_name.split(";"):
        region = _one_region(path)
        if region != OTHER:
            break
    else:
        path = op_name
    if "rematted_computation" in path:
        return region, "remat"
    if "transpose(" in path:
        return region, "bwd"
    return region, "fwd"


def _parse_hlo(text: str) -> dict:
    """Computation name -> [(instruction, op_name or None, calls or None,
    opcode text)], root last."""
    comps, body, root = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line.split("{", 1)[0]:
            body, root = [], None
            comps[m.group(1)] = body
            continue
        if body is None:
            continue
        if line.strip() == "}":
            if root is not None:
                body.append(root)
            body = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        rest = line[m.end():]
        entry = (m.group(2), op.group(1) if op else None,
                 calls.group(1) if calls else None, rest)
        if m.group(1):
            root = entry
        else:
            body.append(entry)
    return comps


def region_map(hlo_text: str) -> dict:
    """Instruction name -> (region, direction) for every instruction of the
    compiled module's text. XLA's host offloading turns the program's
    ``device_put``s into copies that carry no name, so a scope there would
    be lost: an unnamed copy to or from host memory (``S(5)``) is
    ``offload``, and a ``copy-done`` is in the region of the ``copy-start``
    it completes."""
    comps = _parse_hlo(hlo_text)
    out = {}
    for instrs in comps.values():
        for name, op, calls, rest in instrs:
            if op is None and calls in comps:
                op = next((o for _, o, _, _ in reversed(comps[calls]) if o),
                          None)
            if op:
                out[name] = region_of(op)
            elif " copy-start(" in rest and tr.HOST_MEMORY in rest:
                out[name] = ("offload", "fwd")
            else:
                out[name] = (OTHER, "fwd")
        for name, op, _, rest in instrs:
            done = _COPY_DONE.search(rest)
            if op is None and done and done.group(1) in out:
                out[name] = out[done.group(1)]
    return out


def top_level_ops(hlo_text: str, opcodes=("fusion", "convolution", "dot")):
    """Names of the instructions with one of ``opcodes`` outside fused
    computations: the ones that run as ops of their own on the device."""
    comps = _parse_hlo(hlo_text)
    fused = {c for instrs in comps.values() for _, _, c, rest in instrs
             if c and " fusion(" in " " + rest}
    out = []
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for name, _, _, rest in instrs:
            if any(re.search(rf"\s{op}\(", " " + rest) for op in opcodes):
                out.append(name)
    return out


def summary_region_s(summary, rmap: dict, region: str,
                     direction=None) -> float:
    """Device seconds, summed over the chips, of the ops of a
    ``trace_reduce.Summary`` whose instruction name maps to ``region`` (and
    ``direction``). The summary keeps no module, so an op of another module
    in the window that shares a name with one of the step's counts too."""
    ns = 0.0
    for d in summary.devices:
        for s, e, name, _ in d.ops:
            r = rmap.get(name)
            if r and r[0] == region and direction in (None, r[1]):
                ns += e - s
    return ns / 1e9


# ---------------------------------------------------------------------------
# a trace reduced by region, within the step module's runs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepTrace:
    start_ns: float
    end_ns: float
    ops: list  # per device: (start_ns, end_ns, name, region, direction)
    busy: list  # device 0: disjoint union of every op of any module
    host: list  # (start_ns, end_ns, name) on the main Python thread
    steps: list  # device 0's ``Steps`` line: (start_ns, end_ns, name)

    def region_s(self, name: str, direction=None) -> float:
        """Device seconds of one region, summed over the chips."""
        return sum(e - s for ops in self.ops for s, e, _, r, d in ops
                   if r == name and direction in (None, d)) / 1e9

    def step_busy_s(self) -> float:
        """Seconds in which an op of the step module ran, summed over the
        chips."""
        return sum(tr.measure(tr.merge((s, e) for s, e, *_ in ops))
                   for ops in self.ops) / 1e9

    def regions(self, n: int = 10) -> list:
        """The top ``n`` regions by device seconds, each split by
        direction: ``[region, seconds, {"fwd": s, "remat": s, "bwd": s}]``."""
        per = {}
        for ops in self.ops:
            for s, e, _, r, d in ops:
                split = per.setdefault(r, dict.fromkeys(DIRECTIONS, 0.0))
                split[d] += (e - s) / 1e9
        top = sorted(per.items(), key=lambda kv: -sum(kv[1].values()))[:n]
        return [[r, sum(split.values()), split] for r, split in top]

    def gaps(self) -> list:
        return tr.subtract([(self.start_ns, self.end_ns)], self.busy)

    def idle_gaps_program(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps of device 0, each labelled by the
        main thread's host span that explains it (``span_at``)."""
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.span_at(s, e), (e - s) / 1e9] for s, e in gaps]

    def span_at(self, s: float, e: float) -> str:
        """The innermost (shortest) host span that covers at least half of
        ``[s, e)``; where none does, the one that overlaps it most."""
        best, name = None, OTHER
        for hs, he, hn in self.host:
            ov = min(e, he) - max(s, hs)
            if ov <= 0:
                continue
            key = ((0, hs - he) if 2 * ov >= e - s else (-1, ov, hs - he))
            if best is None or key > best:
                best, name = key, hn
        return name


def _is_main_thread(line_name: str, names) -> bool:
    """The Python main thread's lines: the one that holds the ``window``
    span (TraceMe's opened from Python, and the Python tracer's ``$``
    events, left out) and the runtime's own, ``main/<tid>``."""
    return "window" in names or line_name.startswith("main/")


def reduce_file(path, rmap: dict, n_devices: int,
                module: str = STEP_MODULE) -> StepTrace:
    """The trace in ``path`` within its host span ``window``: each device's
    ``XLA Ops`` that run inside an interval of ``module`` on its ``XLA
    Modules`` line, with their region by ``rmap``."""
    pd = tr.load(path)
    window, host, devices = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events]
                names = {n for _, _, n in events}
                if not _is_main_thread(line.name, names):
                    continue
                host += events
                window = window or next(
                    ((s, e) for s, e, n in events if n == "window"), None)
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    if window is None:
        raise ValueError(f"{path}: no 'window' span on the host")
    if len(devices) < n_devices:
        raise ValueError(f"{path}: {len(devices)} device planes with XLA Ops, "
                         f"expected {n_devices}")
    ws, we = window
    inside = lambda s, e: e > ws and s < we  # noqa: E731
    clip = lambda s, e: (max(s, ws), min(e, we))  # noqa: E731
    ops, busy, steps = [], None, []
    for lines in devices[:n_devices]:
        runs = tr.merge(
            (ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in lines["XLA Modules"].events
            if ev.name.split("(")[0] == module)
        kept, every = [], []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if not inside(s, e) or tr.classify(ev.name) == "control":
                continue
            every.append(clip(s, e))
            if any(rs <= s < re_ for rs, re_ in runs):
                name = tr.parse(ev.name)[0]
                kept.append((*clip(s, e), name,
                             *rmap.get(name, (OTHER, "fwd"))))
        ops.append(kept)
        if busy is None:
            busy = tr.merge(every)
            if "Steps" in lines:
                steps = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in lines["Steps"].events
                         if inside(ev.start_ns,
                                   ev.start_ns + ev.duration_ns)]
    host = [(*clip(s, e), n) for s, e, n in host
            if inside(s, e) and not n.startswith("$")]
    return StepTrace(ws, we, ops, busy, host, steps)
