"""The profiler's trace of a window, reduced to what the metrics read.

``capture`` runs a callable under ``jax.profiler`` (Python tracer off)
inside a host span ``bench.window`` and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per operation
that ran (fusions, custom calls such as a Pallas kernel, copies), and
its line ``XLA Modules`` one event per executed program (``jit_<name>``).
The host planes hold the ``TraceAnnotation`` spans the runners record
(names starting ``bench.``).  Times are nanoseconds on one clock.

Busy time is the union of the operation intervals inside the window,
averaged over the chips; idle is the rest of the window.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def _union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Trace:
    """Device operations, programs and host spans of one traced window.

    ``ops`` / ``modules``: per chip, lists of (name, start_ns, end_ns)
    clipped to the window; ``spans``: host (name, start_ns, end_ns)."""

    def __init__(self, ops, modules, spans, window):
        self.ops, self.modules, self.spans = ops, modules, spans
        self.window = window
        w0, w1 = window
        self.window_s = (w1 - w0) * 1e-9
        self.busy_ns = [_union_ns([(s, e) for _, s, e in evs])
                        for evs in ops] or [0]
        self.busy_s = sum(self.busy_ns) / len(self.busy_ns) * 1e-9

    @classmethod
    def from_xspace(cls, path, n_chips: int):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops, modules, spans = {}, {}, []
        for plane in pd.planes:
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if m is not None and line.name == OPS_LINE:
                    ops[int(m.group(1))] = evs
                elif m is not None and line.name == MODULES_LINE:
                    modules[int(m.group(1))] = evs
                elif m is None:
                    spans += [ev for ev in evs
                              if ev[0].startswith(SPAN_PREFIX)]
        wins = [s for s in spans if s[0] == WINDOW_SPAN]
        if not wins:
            raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
        window = (wins[0][1], wins[0][2])
        chips = sorted(ops)[:n_chips]
        clip = lambda evs: [(n, max(s, window[0]), min(e, window[1]))
                            for n, s, e in evs
                            if e > window[0] and s < window[1]]
        return cls([clip(ops[c]) for c in chips],
                   [clip(modules.get(c, [])) for c in chips],
                   spans, window)

    # -- reductions the metric readers use -----------------------------
    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches the
        regular expression, summed over events, averaged over chips."""
        rx = re.compile(pattern)
        tot = [sum(e - s for n, s, e in evs if rx.search(n))
               for evs in self.ops]
        return sum(tot) / max(len(tot), 1) * 1e-9

    def module_seconds(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches."""
        rx = re.compile(pattern)
        tot = [sum(e - s for n, s, e in evs if rx.search(n))
               for evs in self.modules]
        return sum(tot) / max(len(tot), 1) * 1e-9

    def module_count(self, pattern: str) -> float:
        rx = re.compile(pattern)
        tot = [sum(1 for n, _, _ in evs if rx.search(n))
               for evs in self.modules]
        return sum(tot) / max(len(tot), 1)

    def idle_gaps(self):
        """Gaps between the first chip's operations inside the window,
        longest first: (start_ns, end_ns)."""
        evs = sorted((s, e) for _, s, e in self.ops[0]) if self.ops else []
        gaps, t = [], self.window[0]
        for s, e in evs:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        return sorted(gaps, key=lambda g: g[0] - g[1])

    def host_activity(self, s, e) -> str:
        """The innermost runner span that covers most of (s, e)."""
        best, best_cover, best_len = "host: outside the runner's spans", 0, 0
        for n, hs, he in self.spans:
            if n == WINDOW_SPAN:
                continue
            cover = min(e, he) - max(s, hs)
            if cover > 0 and (cover > best_cover or (
                    cover == best_cover and he - hs < best_len)):
                best, best_cover, best_len = n, cover, he - hs
        return best

    def breakdown(self) -> dict:
        """The ten device operations that took most time, and the ten
        longest idle gaps named by what the host was doing in them."""
        per_op = defaultdict(int)
        for n, s, e in (self.ops[0] if self.ops else []):
            per_op[n] += e - s
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = self.idle_gaps()[:10]
        return {"device_ops": [[n[:120], t * 1e-9] for n, t in top],
                "idle_gaps": [[self.host_activity(s, e), (e - s) * 1e-9]
                              for s, e in gaps]}


def capture(fn, log_dir: str, devices):
    """Run ``fn`` under the profiler; returns (fn's result, Trace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return out, Trace.from_xspace(files[0], len(devices))
