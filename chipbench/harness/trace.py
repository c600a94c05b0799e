"""A profiler trace reduced to what the metric readers need.

``TraceView.from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler``
writes, with nothing but JAX.  It keeps, for each TPU device, the program
executions ("XLA Modules" line) and the operations ("XLA Ops" line) with
the name scope each came from, and from the host the ``chipbench.*``
annotations that the harness writes around its own work and around its
calls into the engine's layers.  Times are nanoseconds on the profiler's
one clock; the traced window is the ``chipbench.window`` annotation.

The view is plain data (``to_json``/``from_json``), so readers are tested
on a small recorded trace without a chip.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

HOST_PREFIX = "chipbench."
# the engine's jitted step bodies (inference/backends/bodies.py)
STEP_PROGRAMS = {"decode": re.compile(r"paged_decode_body"),
                 "prefill": re.compile(r"paged_prefill_body")}

CONTAINERS = ("while", "conditional", "call")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, wire type, value) of one protobuf message; nested
    messages and strings come back as memoryview slices, unparsed."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, wire, val


def op_metadata(path) -> dict:
    """Per TPU plane, each operation's name (the HLO instruction text) ->
    its short name, name scope (``tf_op``) and ``hlo_category``, read from
    the XPlane's event metadata (the fields ``ProfileData`` does not show).
    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4, stat_metadata
    = 5; XEventMetadata: name = 2, display_name = 4, stats = 5; XStat:
    metadata_id = 1, str_value = 5, ref_value = 7."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for num, _, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                sid = sname = None
                for g, _, w in _fields(v):
                    if g == 2:
                        for h, _, x in _fields(w):
                            if h == 1:
                                sid = x
                            elif h == 2:
                                sname = bytes(x).decode()
                stat_names[sid] = sname
        if not name.startswith("/device:TPU:"):
            continue
        table = {}
        for entry in events:
            for g, _, em in _fields(entry):
                if g != 2:
                    continue
                rec = {"name": "", "short": "", "tf_op": "", "hlo_category": ""}
                for h, _, x in _fields(em):
                    if h == 2:
                        rec["name"] = bytes(x).decode()
                    elif h == 4:
                        rec["short"] = bytes(x).decode()
                    elif h == 5:
                        sid, val = None, None
                        for k, _, y in _fields(x):
                            if k == 1:
                                sid = y
                            elif k == 5:
                                val = bytes(y).decode()
                            elif k == 7:
                                val = stat_names.get(y)
                        key = stat_names.get(sid)
                        if key in ("tf_op", "hlo_category") and val is not None:
                            rec[key] = val
                table[rec["name"]] = rec
        out[name] = table
    return out


@dataclass
class TraceView:
    window: tuple                      # (t0, t1) ns
    devices: dict                      # device id -> {"programs": [...], "ops": [...]}
    host: list = field(default_factory=list)   # [t0, t1, name, args]

    # ------------------------------------------------------------ reading
    @classmethod
    def from_xplane(cls, path) -> "TraceView":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(str(path))
        meta = op_metadata(path)
        devices, host = {}, []
        for plane in data.planes:
            name = plane.name
            if name.startswith("/device:TPU:") and "Core" not in name:
                dev = int(name.rsplit(":", 1)[1])
                devices[dev] = _read_device(plane, meta.get(name, {}))
            elif name.startswith("/host:CPU"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(HOST_PREFIX):
                            host.append([ev.start_ns,
                                         ev.start_ns + ev.duration_ns,
                                         ev.name[len(HOST_PREFIX):],
                                         dict(ev.stats)])
        host.sort(key=lambda h: h[0])
        win = [h for h in host if h[2] == "window"]
        if not win:
            raise ValueError(f"{path}: no chipbench.window annotation")
        return cls(window=(win[0][0], win[0][1]), devices=devices,
                   host=host)

    @classmethod
    def from_dir(cls, trace_dir) -> "TraceView":
        found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_xplane(found[-1])

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "devices": {str(k): v for k, v in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "TraceView":
        return cls(window=tuple(d["window"]),
                   devices={int(k): v for k, v in d["devices"].items()},
                   host=d["host"])

    @classmethod
    def load(cls, path) -> "TraceView":
        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "rt") as fh:
            return cls.from_json(json.load(fh))

    # ------------------------------------------------------------ queries
    @property
    def seconds(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clip(self, iv) -> list:
        t0, t1 = self.window
        return [(max(a, t0), min(b, t1)) for a, b, *_ in iv
                if b > t0 and a < t1]

    def busy_ns(self, dev: int) -> float:
        return union_length(self.clip(self.devices[dev]["ops"]))

    def programs(self, dev: int, kind: str) -> list:
        """Executions of the engine's ``kind`` step program, inside the
        window, as [t0, t1, name]."""
        pat = STEP_PROGRAMS[kind]
        t0, t1 = self.window
        return [p for p in self.devices[dev]["programs"]
                if pat.search(p[2]) and p[0] >= t0 and p[1] <= t1]

    def ops_within(self, dev: int, spans: list) -> list:
        """Leaf ops of device ``dev`` that start inside one of ``spans``
        (a loop's own event, which spans its body's ops, is left out)."""
        ops = [o for o in self.devices[dev]["ops"]
               if o[4] not in CONTAINERS]
        out, j = [], 0
        spans = sorted(spans)
        for op in ops:
            while j < len(spans) and spans[j][1] <= op[0]:
                j += 1
            if j < len(spans) and spans[j][0] <= op[0] < spans[j][1]:
                out.append(op)
        return out

    def host_spans(self, name: str) -> list:
        t0, t1 = self.window
        return [h for h in self.host
                if h[2] == name and h[0] >= t0 and h[1] <= t1]

    def host_label(self, t: float) -> str:
        """The innermost harness annotation open at host time ``t``."""
        best = None
        for h in self.host:
            if h[0] > t:
                break
            if h[1] >= t and h[2] != "window" and \
                    (best is None or h[0] >= best[0]):
                best = h
        return best[2] if best else "outside"

    def idle_gaps(self, dev: int) -> list:
        """[t0, t1] intervals of the window with no op on ``dev``."""
        t0, t1 = self.window
        gaps, cur = [], t0
        for a, b in merged(self.clip(self.devices[dev]["ops"])):
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < t1:
            gaps.append((cur, t1))
        return gaps


def _read_device(plane, meta: dict) -> dict:
    """Program executions as [t0, t1, name] and operations as [t0, t1,
    short name, name scope, hlo category]."""
    programs, ops = [], []
    for line in plane.lines:
        if line.name == "XLA Modules":
            for ev in line.events:
                programs.append([ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name])
        elif line.name == "XLA Ops":
            for ev in line.events:
                m = meta.get(ev.name, {})
                ops.append([ev.start_ns, ev.start_ns + ev.duration_ns,
                            m.get("short") or ev.name.split(" ", 1)[0],
                            m.get("tf_op", ""), m.get("hlo_category", "")])
    programs.sort(key=lambda p: p[0])
    ops.sort(key=lambda o: o[0])
    return {"programs": programs, "ops": ops}


def merged(iv) -> list:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_length(iv) -> float:
    return float(sum(b - a for a, b in merged(iv)))
