"""Reading a profiler trace: the shared code of the trace-based metrics.

A :class:`Trace` holds, on one clock in nanoseconds,

* per device: the intervals of the operations that ran (the ``XLA Ops``
  line of each ``/device:TPU:<n>`` plane) and the executions of whole
  programs (its ``XLA Modules`` line, named ``<module>(<fingerprint>)``);
* the harness's own host spans (``TraceAnnotation`` names starting with
  ``bench.``), which mark the traced window and what the host was doing.

:meth:`Trace.from_xplane` reads an ``.xplane.pb``; :meth:`Trace.from_events`
builds one from plain lists, which is how the tests feed it.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

#: the host span that brackets the traced window
WINDOW = "bench.traced"
#: collective operations, by the HLO op names XLA gives them
COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                  "collective-permute", "all-to-all")


@dataclasses.dataclass
class Event:
    name: str
    start: int      # ns
    dur: int        # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(intervals, holes) -> list[tuple[int, int]]:
    """``intervals`` minus the union of ``holes``."""
    holes = union(holes)
    out = []
    for s, e in union(intervals):
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(op_name: str) -> bool:
    """An ``XLA Ops`` event whose HLO instruction is a collective (the
    event name is the instruction text, ``%name = type op(...)``)."""
    head = op_name.split("(", 1)[0]
    return any(c in head for c in COLLECTIVE_OPS)


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]          # device id -> op events
    modules: dict[int, list[Event]]      # device id -> program executions
    spans: list[Event]                   # harness host spans

    @classmethod
    def from_events(cls, ops, modules, spans) -> "Trace":
        mk = lambda evs: [Event(n, int(s), int(d)) for n, s, d in evs]
        return cls({int(k): mk(v) for k, v in ops.items()},
                   {int(k): mk(v) for k, v in modules.items()}, mk(spans))

    @classmethod
    def from_xplane(cls, path: Path) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(str(path))
        ops, modules, spans = {}, {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = int(plane.name.rsplit(":", 1)[1])
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops[dev] = [Event(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
                    elif line.name == "XLA Modules":
                        modules[dev] = [Event(e.name, e.start_ns,
                                              e.duration_ns)
                                        for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith("bench."))
        return cls(ops, modules, spans)

    @classmethod
    def from_dir(cls, log_dir: Path) -> "Trace":
        files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_xplane(files[-1])

    # -- the window ----------------------------------------------------------
    def window(self) -> tuple[int, int]:
        w = [s for s in self.spans if s.name == WINDOW]
        if not w:
            raise ValueError(f"no {WINDOW} span in the trace")
        return w[0].start, w[0].end

    @property
    def devices(self) -> list[int]:
        return sorted(self.ops)

    def busy_ns(self, dev: int) -> int:
        t0, t1 = self.window()
        return covered(clip([(e.start, e.end) for e in self.ops.get(dev, [])],
                            t0, t1))

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns(d) for d in self.devices) / len(self.devices) / 1e9

    def window_s(self) -> float:
        t0, t1 = self.window()
        return (t1 - t0) / 1e9

    # -- programs ------------------------------------------------------------
    def program_named_by(self, span: str, dev: int = 0) -> str | None:
        """The program (``XLA Modules`` name) that ran longest inside the
        host span ``span``: the harness calls one program there and waits
        for it, which is how a program is told apart from others with the
        same module name."""
        marks = [s for s in self.spans if s.name == span]
        if not marks:
            return None
        m = marks[-1]
        inside = [e for e in self.modules.get(dev, [])
                  if e.start >= m.start and e.end <= m.end]
        return max(inside, key=lambda e: e.dur).name if inside else None

    def program_calls(self, name: str, dev: int = 0) -> list[Event]:
        """Executions of program ``name`` inside the window."""
        t0, t1 = self.window()
        return [e for e in self.modules.get(dev, [])
                if e.name == name and e.start >= t0 and e.end <= t1]

    def program_calls_by_prefix(self, prefix: str, dev: int = 0) -> list[Event]:
        """Executions inside the window of programs whose module name
        starts with ``prefix`` (``jit_<function>``)."""
        t0, t1 = self.window()
        return [e for e in self.modules.get(dev, [])
                if e.name.startswith(prefix + "(") and e.start >= t0
                and e.end <= t1]

    # -- collectives ---------------------------------------------------------
    def exposed_collective_ns(self, dev: int = 0) -> int:
        """Time in collective ops during which no other op runs."""
        t0, t1 = self.window()
        evs = self.ops.get(dev, [])
        coll = clip([(e.start, e.end) for e in evs if is_collective(e.name)],
                    t0, t1)
        other = clip([(e.start, e.end) for e in evs
                      if not is_collective(e.name)], t0, t1)
        return covered(subtract(coll, other))

    # -- what the driver copies into the ledger ------------------------------
    def host_span_at(self, t: int) -> str:
        """The innermost harness span covering instant ``t``."""
        inner = [s for s in self.spans
                 if s.start <= t <= s.end and s.name != WINDOW]
        return min(inner, key=lambda s: s.dur).name if inner else "host:outside-spans"

    def top_ops(self, dev: int = 0, top: int = 10) -> list[tuple[str, int]]:
        """Operations by total device time in the window, named by their
        HLO instruction (the text before `` = ``)."""
        t0, t1 = self.window()
        totals: dict[str, int] = {}
        for e in self.ops.get(dev, []):
            if e.start >= t0 and e.end <= t1:
                n = e.name.split(" = ", 1)[0]
                totals[n] = totals.get(n, 0) + e.dur
        return sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    def breakdown(self, top: int = 10) -> dict:
        """The programs that took most device time (their operations, when
        one program runs the window), and the longest idle gaps named by
        what the host was doing."""
        t0, t1 = self.window()
        dev = self.devices[0] if self.devices else 0
        totals: dict[str, int] = {}
        for e in self.modules.get(dev, []):
            if e.start >= t0 and e.end <= t1:
                totals[e.name] = totals.get(e.name, 0) + e.dur
        device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        if len(device_ops) < 3:
            device_ops = self.top_ops(dev, top)
        busy = union(clip([(e.start, e.end) for e in self.ops.get(dev, [])],
                          t0, t1))
        gaps, cur = [], t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            gaps.append((cur, t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = [[self.host_span_at((s + e) // 2), (e - s) / 1e9]
                for s, e in gaps[:top]]
        return {"device_ops": [[n, ns / 1e9] for n, ns in device_ops],
                "idle_gaps": idle}
