"""Reduce a profiler trace of the measured window to device-time figures.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone. The window is the host span ``window``
that the harness opens around the loop. On a TPU each device plane
(``/device:TPU:<n>``) has two lines that matter: ``XLA Ops``, one event per
HLO instruction executed, named by the instruction's own HLO text
(``%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...``), with control
flow (``while``, ``conditional``, ``call``) spanning its body's events; and
``Async XLA Ops``, one event per asynchronous operation from its start to
its done. Each instruction falls in one class:

- ``matmul``: a ``convolution`` (XLA's dot on TPU) or a fusion rooted in
  one (``kind=kOutput``);
- ``collective``: all-gather, reduce-scatter, all-reduce, all-to-all,
  collective-permute, including the start and done halves of async ones;
- ``host_copy``: the start or done of a copy whose result lies in host
  memory (memory space ``S(5)``); a done is the core waiting for it;
- ``wait``: the done of any other async operation (copies and slices
  between on-chip memories);
- ``compute``: every other instruction.

Busy time is the union of all instructions (control flow left out, its body
counted). A collective or a host copy is in flight from its async start to
its done (``Async XLA Ops``), or while a synchronous one runs, and exposed
where it is in flight and no ``matmul`` or ``compute`` instruction runs.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import re
from pathlib import Path

COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")
CONTROL_FLOW = ("while", "conditional", "call")
HOST_MEMORY = "S(5)"
HOST_SPANS = ("make_batch", "dispatch_step", "read_loss")
_HLO = re.compile(r"%([^ ]+) = (.*?[\]})]) ([a-z][a-z0-9-]*)\(")


def parse(text: str) -> tuple:
    """(instruction name, result shape text, opcode) of one HLO line."""
    m = _HLO.match(text)
    if m is None:
        return text, "", ""
    return m.group(1), m.group(2), m.group(3)


def classify(text: str) -> str:
    name, shape, op = parse(text)
    if op in CONTROL_FLOW:
        return "control"
    if any(w in name or w in op for w in COLLECTIVE_WORDS):
        return "collective"
    if op.endswith(("-start", "-done")) or op in ("async-start",
                                                   "async-done"):
        if op.startswith("copy") and HOST_MEMORY in shape:
            return "host_copy"
        return "wait" if op.endswith("done") else "compute"
    if op == "convolution" or (op == "fusion" and "kind=kOutput" in text):
        return "matmul"
    return "compute"


def in_flight(text: str):
    """The class an ``Async XLA Ops`` event keeps in flight, if any."""
    name, shape, op = parse(text)
    if any(w in name or w in op for w in COLLECTIVE_WORDS):
        return "collective"
    if op.startswith("copy") and HOST_MEMORY in shape:
        return "host_copy"
    return None


def merge(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Device:
    ops: list  # (start_ns, end_ns, name, cls), clipped to the window
    flights: list  # (start_ns, end_ns, cls) of async operations in flight

    def union(self, classes=None) -> list:
        return merge((s, e) for s, e, _, c in self.ops
                     if classes is None or c in classes)

    def class_ns(self, cls: str) -> float:
        return float(sum(e - s for s, e, _, c in self.ops if c == cls))

    def flight(self, cls: str) -> list:
        return merge([(s, e) for s, e, c in self.flights if c == cls]
                     + [(s, e) for s, e, _, c in self.ops if c == cls])

    def exposed_ns(self, cls: str) -> float:
        return measure(subtract(self.flight(cls),
                                self.union({"matmul", "compute"})))


@dataclasses.dataclass
class Summary:
    start_ns: float
    end_ns: float
    devices: list
    host_spans: list  # (start_ns, end_ns, name) inside the window

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(measure(d.union()) for d in self.devices) / 1e9 / len(
            self.devices)

    def class_s(self, cls: str) -> float:
        """Device seconds of one class, summed over the devices."""
        return sum(d.class_ns(cls) for d in self.devices) / 1e9

    def present(self, cls: str) -> bool:
        return any(d.flight(cls) for d in self.devices)

    def exposed_share(self, cls: str) -> float:
        """Largest share of the window, over the devices, in which ``cls``
        runs alone."""
        w = self.end_ns - self.start_ns
        return max(d.exposed_ns(cls) for d in self.devices) / w

    def gaps(self) -> list:
        """Idle intervals of the first device within the window."""
        busy = self.devices[0].union()
        return subtract([(self.start_ns, self.end_ns)], busy)

    def breakdown(self, n: int = 10) -> dict:
        per_op = {}
        for d in self.devices:
            for s, e, name, cls in d.ops:
                key = f"{name} ({cls})"
                per_op[key] = per_op.get(key, 0.0) + (e - s)
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
        scale = 1e9 * len(self.devices)
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return {"device_ops": [[k, v / scale] for k, v in top],
                "idle_gaps": [[self.host_at(s, e), (e - s) / 1e9]
                              for s, e in gaps]}

    def host_at(self, s: float, e: float) -> str:
        """The host span that overlaps the gap [s, e) most."""
        best, name = 0.0, "other"
        for hs, he, hn in self.host_spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        return name


def load(path):
    """The trace in ``path``: an ``.xplane.pb``, or one compressed with gzip
    (``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def reduce_file(path, n_devices: int) -> Summary:
    pd = load(path)
    window, spans, raw, flights = None, [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name))
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            raw.append([(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in lines["XLA Ops"].events])
            async_ops = lines.get("Async XLA Ops")
            flights.append([] if async_ops is None else [
                (ev.start_ns, ev.start_ns + ev.duration_ns, in_flight(ev.name))
                for ev in async_ops.events])
    if window is None:
        raise ValueError(f"{path}: no 'window' span on the host")
    if len(raw) < n_devices:
        raise ValueError(f"{path}: {len(raw)} device planes with XLA Ops, "
                         f"expected {n_devices}")
    ws, we = window
    clip = lambda s, e: (max(s, ws), min(e, we))  # noqa: E731
    devices = []
    for ops, fl in zip(raw[:n_devices], flights):
        kept = []
        for s, e, text in ops:
            cls = classify(text)
            if e > ws and s < we and cls != "control":
                kept.append((*clip(s, e), parse(text)[0], cls))
        devices.append(Device(kept, [(*clip(s, e), c) for s, e, c in fl
                                     if c and e > ws and s < we]))
    spans = [(s, e, n) for s, e, n in spans if e > ws and s < we]
    return Summary(ws, we, devices, spans)


def reduce_dir(directory, n_devices: int) -> Summary:
    files = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise ValueError(f"no .xplane.pb under {directory}")
    return reduce_file(files[-1], n_devices)
