"""Reduction of a profiler trace to the numbers the per-layer metrics read.

From one ``.xplane.pb`` (``jax.profiler`` trace): every operation that ran
on a device, with its program (the jitted function: ``jit_segment``,
``jit_extend``, ...), its duration, and the union of those intervals (the
busy time); the idle gaps between them, each labelled with what the host's
Python threads were doing then; and the ``breakdown`` a traced run prints.

Device operations are the events of the ``XLA Ops`` lines of the
``/device:`` planes (a TPU), or, where a trace has no device plane (the
CPU backend), the host events that carry an ``hlo_op`` statistic.  A
program's executions are the events of the ``XLA Modules`` lines, or the
``hlo_module`` statistic of its operations.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os


@dataclasses.dataclass
class Op:
    start: int            # ns on the trace's clock
    dur: int
    name: str
    module: str
    text: str             # name plus string statistics, for name matching
    device: str


@dataclasses.dataclass
class Summary:
    ops: list                       # device operations (Op)
    modules: dict                   # module name -> [count, total ns]
    host: list                      # (start, dur, thread, name) host spans
    devices: list                   # device plane names
    t0: int                         # traced span on the trace clock
    t1: int

    # -- sums -------------------------------------------------------------
    def op_seconds(self, match) -> float:
        """Device seconds of the operations whose name or statistics
        contain ``match``."""
        return sum(o.dur for o in self.ops if match in o.text) * 1e-9

    def module_calls(self, name: str) -> tuple:
        """(executions, device seconds) of the programs whose name
        contains ``name``."""
        n = t = 0
        for m, (c, d) in self.modules.items():
            if name in m:
                n += c
                t += d
        return n, t * 1e-9

    def busy_seconds(self) -> float:
        """Union of the operation intervals, averaged over devices."""
        per = collections.defaultdict(list)
        for o in self.ops:
            per[o.device].append((o.start, o.start + o.dur))
        if not per:
            return 0.0
        tot = 0
        for iv in per.values():
            tot += sum(b - a for a, b in _union(iv))
        return tot / len(per) * 1e-9

    def window_seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle intervals of the first device, each as
        (label, seconds): the label names the innermost host span that
        covers the gap's middle."""
        dev = self.devices[0] if self.devices else None
        iv = _union([(o.start, o.start + o.dur) for o in self.ops
                     if o.device == dev])
        edges = [self.t0] + [x for ab in iv for x in ab] + [self.t1]
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                out.append((b - a, a))
        out.sort(reverse=True)
        # label by the thread that dispatches the device programs: the one
        # with the most ``PjitFunction`` spans
        count = collections.Counter(th for _, _, th, nm in self.host
                                    if nm.startswith("PjitFunction"))
        main = count.most_common(1)[0][0] if count else None
        return [(self._host_label((a + a + d) // 2, main), d * 1e-9)
                for d, a in out[:n]]

    def _host_label(self, t: int, main) -> str:
        """The innermost span covering ``t`` on thread ``main``."""
        best = None
        for s, d, thread, name in self.host:
            if main is not None and thread != main:
                continue
            if s <= t < s + d and (best is None or d < best[0]):
                best = (d, name)
        return best[1] if best else "no host span"

    def leaves(self) -> list:
        """Operations that contain no other operation (a loop's events
        span their bodies' operations on the same line)."""
        out = []
        by_dev = collections.defaultdict(list)
        for o in self.ops:
            by_dev[o.device].append(o)
        for ops in by_dev.values():
            ops.sort(key=lambda o: (o.start, -o.dur))
            stack = []                  # [op, is_leaf]
            for o in ops:
                while stack and stack[-1][0].start + stack[-1][0].dur \
                        <= o.start:
                    top = stack.pop()
                    if top[1]:
                        out.append(top[0])
                if stack:
                    stack[-1][1] = False
                stack.append([o, True])
            out += [op for op, leaf in stack if leaf]
        return out

    def top_ops(self, n: int = 10) -> list:
        """The leaf operations that took most device time, by name (the
        HLO text's name, ``%name.N = ...`` cut to ``name``)."""
        agg = collections.Counter()
        for o in self.leaves():
            agg[_short(o.name)] += o.dur
        return [(k, v * 1e-9) for k, v in agg.most_common(n)]

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.top_ops(10)],
                "idle_gaps": [[k, v] for k, v in self.gaps(10)]}


def _short(name: str) -> str:
    """``%paged_attention_decode.6 = f32[...] ...`` -> the op's name with
    its numeric suffix dropped (``paged_attention_decode``)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:                              # noqa: BLE001
        return {}


def start(log_dir: str) -> None:
    """Start the profiler with the host tracer (XLA's TraceMe spans:
    ``PjitFunction``, transfers) and without the Python tracer, which would
    slow the serving thread it records."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce(path: str, t0: int | None = None, t1: int | None = None
           ) -> Summary:
    """Read the trace at ``path`` (a file or a profiler log directory);
    ``t0``/``t1`` clip it to a span of the trace clock (ns)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, host, devices = [], [], []
    span = None
    modules = collections.defaultdict(lambda: [0, 0])
    mod_iv = collections.defaultdict(list)
    hlo_ops = []
    has_dev = any(p.name.startswith("/device:") for p in pd.planes)
    for plane in pd.planes:
        st = _stats(plane)
        if "profile_start_time" in st and "profile_stop_time" in st:
            span = int(st["profile_stop_time"]) - int(st["profile_start_time"])
        is_dev = plane.name.startswith("/device:")
        if is_dev:
            devices.append(plane.name)
        for line in plane.lines:
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if is_dev and line.name == "XLA Modules":
                    mod_iv[plane.name].append((s, s + d, ev.name))
                elif is_dev and line.name == "XLA Ops":
                    # the name is the operation's HLO text: it holds the
                    # custom call's (the kernel's) name
                    ops.append(Op(s, d, ev.name, "", ev.name, plane.name))
                elif not is_dev and plane.name.startswith("/host:"):
                    st = {} if has_dev else _stats(ev)
                    if "hlo_op" in st:
                        hlo_ops.append((s, d, ev.name, st))
                    elif d > 0:
                        host.append((s, d, line.name, ev.name))
    if not devices:                     # the CPU backend: ops on host lines
        for s, d, name, st in hlo_ops:
            ops.append(Op(s, d, name, str(st.get("hlo_module", "")),
                          " ".join([name] + [str(v) for v in st.values()
                                             if isinstance(v, str)]),
                          "/host:CPU"))
        devices = ["/host:CPU"] if ops else []
    for dev, iv in mod_iv.items():      # programs: executions and time
        iv.sort()
        for s, e, name in iv:
            modules[name][0] += 1
            modules[name][1] += e - s
    if not mod_iv:
        seen = set()
        for o in ops:
            key = (o.module, o.device)
            modules[o.module][1] += o.dur
            seen.add(key)
        for m in {o.module for o in ops}:
            modules[m][0] = 0
    if span is not None:        # event times count from the trace's start
        lo, hi = 0, span
    else:
        lo = min([o.start for o in ops] + [s for s, *_ in host], default=0)
        hi = max([o.start + o.dur for o in ops]
                 + [s + d for s, d, *_ in host], default=0)
    t0 = lo if t0 is None else t0
    t1 = hi if t1 is None else t1
    ops = [o for o in ops if o.start >= t0 and o.start + o.dur <= t1]
    return Summary(ops, dict(modules), host, devices, t0, t1)
