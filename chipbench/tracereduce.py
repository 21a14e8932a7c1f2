"""From a profiler trace to device busy time, idle gaps and attributed time.

Two steps, kept apart so that the second can be tested on a small recorded
trace without a chip:

* ``extract(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
  into plain lists: the host's ``chipbench.<span>`` annotations and JAX's
  ``PjitFunction(<name>)`` dispatches, and, per device plane, its XLA
  module and op events (an op by its HLO name, ``%fusion.12``).  Times
  are nanoseconds on the trace's clock.
* ``reduce(trace)`` computes, inside the ``chipbench.window`` annotation:
  - busy: the union of the device's op intervals (module intervals where a
    plane has no op line); idle share = 1 - busy / window, per device;
  - device time per host span: each module execution is attributed to the
    span that dispatched it, found by name: the module ``jit_<f>`` was
    launched by the ``PjitFunction(<f>)`` event, and that event lies inside
    one ``chipbench.<span>``;
  - the device ops that took most time, and the longest idle gaps named by
    the innermost span the host was in when the gap began.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW = SPAN_PREFIX + "window"
TOP = 10


def module_base(name: str) -> str:
    """``jit_step(123)`` / ``jit_step.4`` -> ``step``."""
    base = re.split(r"[(\s]", name, maxsplit=1)[0]
    base = re.sub(r"\.\d+$", "", base)
    return base[4:] if base.startswith("jit_") else base


def extract(path: str) -> Dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    host: List[list] = []
    devices: Dict[str, Dict] = {}
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, "PjitFunction(")):
                        host.append([e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)])
        elif m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        dev["modules"].append(
                            [e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)])
                elif line.name == "XLA Ops":
                    for e in line.events:
                        dev["ops"].append([e.name.split(" = ")[0],
                                           int(e.start_ns),
                                           int(e.start_ns + e.duration_ns)])
            devices[m.group(1)] = dev
    return {"host": host, "devices": devices}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, w0: int, w1: int) -> int:
    return max(0, min(e, w1) - max(s, w0))


def _leaf_ops(ops: List[list]) -> List[list]:
    """Ops that hold no other op: a ``while`` spans its body's ops, which
    are counted themselves."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < o[2] and nxt[2] <= o[2])]


def _spans(host: List[list]) -> List[tuple]:
    return sorted((n[len(SPAN_PREFIX):], s, e) for n, s, e in host
                  if n.startswith(SPAN_PREFIX) and n != WINDOW)


def _innermost(spans: List[tuple], t: int) -> Optional[str]:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def reduce(trace: Dict) -> Dict:
    host = trace["host"]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows or not trace["devices"]:
        return {}
    w0, w1 = windows[0]
    spans = _spans(host)
    # launcher of each jitted function: the innermost span around its
    # PjitFunction dispatch
    launcher: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    for n, s, e in host:
        m = re.match(r"PjitFunction\((.*)\)$", n)
        if m and w0 <= s <= w1:
            launcher[m.group(1)][_innermost(spans, s) or "unspanned"] += 1
    per_device = {}
    by_span: Dict[str, float] = collections.Counter()
    op_time: Dict[str, float] = collections.Counter()
    gaps: List[tuple] = []
    for dev, d in sorted(trace["devices"].items()):
        base = d["ops"] or d["modules"]
        busy_iv = _union([(s, e) for _, s, e in base
                          if _clip(s, e, w0, w1) > 0])
        busy = sum(_clip(s, e, w0, w1) for s, e in busy_iv)
        per_device[dev] = {"busy_s": busy / 1e9,
                           "idle_share": 1.0 - busy / (w1 - w0)}
        for name, s, e in d["modules"]:
            t = _clip(s, e, w0, w1)
            if t:
                who = launcher.get(module_base(name))
                span = who.most_common(1)[0][0] if who else "unattributed"
                by_span[span] += t / 1e9
        for name, s, e in _leaf_ops(d["ops"]):
            t = _clip(s, e, w0, w1)
            if t:
                op_time[name] += t / 1e9
        edges = [w0] + [x for iv in busy_iv for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, w0), min(b, w1)
            if b > a:
                gaps.append((b - a, a))
    n = len(per_device)
    gaps = [(_innermost(spans, a) or "outside spans", g / 1e9)
            for g, a in sorted(gaps, reverse=True)[:TOP]]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(p["busy_s"] for p in per_device.values()) / n,
        "idle_share_worst": max(p["idle_share"]
                                for p in per_device.values()),
        "per_device": per_device,
        "device_s_by_span": {k: v / n for k, v in by_span.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in
                           sorted(op_time.items(), key=lambda kv: -kv[1])
                           [:TOP]],
            "idle_gaps": [[name, s] for name, s in gaps],
        },
    }


class Tracer:
    """The profiler around the window, with the window's own annotation."""

    def __init__(self):
        self.dir = None
        self._window = None

    def start(self) -> "Tracer":
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        return self

    def stop_and_reduce(self) -> Dict:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return reduce(extract(files[0])) if files else {}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
