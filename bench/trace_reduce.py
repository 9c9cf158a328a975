"""Reduce one profiler trace (``.xplane.pb``) to the numbers the per-layer
readers and the result's ``device`` and ``breakdown`` take.

Planes whose name starts with ``/device:`` and that hold an ``XLA Ops``
line are the chips. On each, the ``XLA Ops`` line holds one event per
device operation and the ``XLA Modules`` line one per program execution.
The host plane's threads (a line each) hold the program's own stage
spans (names starting ``repro.``) and the harness's ``bench.window``,
which spans the measured window; everything is clipped to it.

- busy: the union of device-op intervals in the window, averaged over the
  chips; idle share is 1 - busy / window;
- ``device_ops``: device time per operation name, largest first;
- ``idle_gaps``: the idle time of the first chip, cut at every boundary
  of a ``repro.`` span and split, by time, by the innermost ``repro.``
  span open on each host thread: a piece's label is the '+'-joined
  sorted names of those spans ("none" where no thread was in one);
- ``programs``: per program name (the module name without its ``(n)``
  suffix), how many times it ran and its device time.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
SPAN_PREFIX = "repro."
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_OP_HEAD = re.compile(r"^(%[^ ]+ = [a-z0-9]+\[[0-9,]*\])")


def op_name(text: str) -> str:
    """An HLO op's event name cut to its name and result shape
    ("%fusion.1 = f32[524288,8]"), so its time adds up across runs."""
    m = _OP_HEAD.match(text)
    return m.group(1) if m else text[:80]


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, merged [start, end) intervals of an [n, 2] array."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if iv.size == 0:
        return iv.reshape(0, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def _events(line) -> Tuple[List[str], np.ndarray]:
    names, iv = [], []
    for e in line.events:
        names.append(e.name)
        iv.append((e.start_ns, e.start_ns + e.duration_ns))
    return names, np.asarray(iv, np.float64).reshape(-1, 2)


def _innermost(iv: np.ndarray, codes: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """One thread's spans ([n, 2] intervals, name codes [n]), which nest
    as one thread's annotations do, -> the change points of the innermost
    span open: times [m] (non-decreasing) and the code from each on, -1
    where none is open. A span that outlasts its parent is cut at the
    parent's end."""
    times, labels, stack = [], [], []   # stack: (end, code)

    def pop_until(t):
        while stack and stack[-1][0] <= t:
            end = stack.pop()[0]
            times.append(end)
            labels.append(stack[-1][1] if stack else -1)

    for i in np.lexsort((-iv[:, 1], iv[:, 0])):    # by start, outer first
        s, e = iv[i]
        pop_until(s)
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, int(codes[i])))
        times.append(s)
        labels.append(int(codes[i]))
    pop_until(np.inf)
    return np.asarray(times, np.float64), np.asarray(labels, np.int64)


def split_idle(gaps: np.ndarray, threads: Dict[object, Dict[str, list]],
               top: int) -> List[list]:
    """Idle intervals ([g, 2], disjoint, sorted) -> [[label, seconds]] of
    the ``top`` largest labels, each idle piece (the gaps cut at every
    span boundary of every thread) labelled by the innermost span open on
    each thread (``threads``: per thread, span name -> intervals)."""
    if gaps.size == 0:
        return []
    names = sorted({n for spans in threads.values() for n in spans})
    code = {n: c for c, n in enumerate(names)}
    timelines = []
    for spans in threads.values():
        iv = np.concatenate([np.asarray(v, np.float64).reshape(-1, 2)
                             for v in spans.values()])
        codes = np.concatenate([np.full(len(v), code[n])
                                for n, v in spans.items()])
        timelines.append(_innermost(iv, codes))
    cuts = np.unique(np.concatenate([gaps.ravel()]
                                    + [t for t, _ in timelines]))
    cuts = cuts[(cuts >= gaps[0, 0]) & (cuts <= gaps[-1, 1])]
    mid, dur = (cuts[:-1] + cuts[1:]) / 2, np.diff(cuts)
    j = np.searchsorted(gaps[:, 0], mid, side="right") - 1
    idle = (j >= 0) & (mid < gaps[np.maximum(j, 0), 1])
    mid, dur = mid[idle], dur[idle]
    # a first column of "none" (-1), so that a trace without spans stacks
    cols = [np.full(mid.size, -1, np.int64)]
    for times, labels in timelines:
        k = np.searchsorted(times, mid, side="right") - 1
        cols.append(np.where(k >= 0, labels[np.maximum(k, 0)], -1))
    rows, inv = np.unique(np.stack(cols, 1), axis=0, return_inverse=True)
    secs = np.bincount(inv.ravel(), weights=dur, minlength=len(rows))
    by = collections.Counter()
    for row, sec in zip(rows, secs):
        label = "+".join(sorted({names[c] for c in row if c >= 0}))
        by[label or "none"] += sec * 1e-9
    return [[k, float(v)] for k, v in by.most_common(top)]


def reduce_trace(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, win = [], []
    threads = collections.defaultdict(lambda: collections.defaultdict(list))
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and any(
                line.name == "XLA Ops" for line in plane.lines):
            chips.append(plane)
        elif name.startswith("/host:"):
            for t, line in enumerate(plane.lines):
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == WINDOW:
                        win.append(iv)
                    elif e.name.startswith(SPAN_PREFIX):
                        threads[(name, t)][e.name.split("#")[0]].append(iv)
    if not win or not chips:
        return {"chips": len(chips), "window_s": None, "busy_s": None}
    win = np.asarray(win, np.float64)
    lo, hi = float(win[:, 0].min()), float(win[:, 1].max())
    busy, ops, progs = [], collections.Counter(), {}
    first_union = None
    for chip in chips:
        op_iv = np.zeros((0, 2))
        for line in chip.lines:
            if line.name == "XLA Ops":
                names, iv = _events(line)
                keep = (iv[:, 1] > lo) & (iv[:, 0] < hi)
                iv = iv[keep]
                clipped = _clip(iv, lo, hi)
                for n, (s, e) in zip(np.asarray(names, object)[keep],
                                     np.clip(iv, lo, hi)):
                    ops[op_name(n)] += (e - s) * 1e-9
                op_iv = np.concatenate([op_iv, clipped])
            elif line.name == "XLA Modules":
                names, iv = _events(line)
                for n, (s, e) in zip(names, iv):
                    if e <= lo or s >= hi:
                        continue
                    key = _MODULE_SUFFIX.sub("", n)
                    p = progs.setdefault(key, {"runs": 0, "device_s": 0.0})
                    p["runs"] += 1
                    p["device_s"] += float(min(e, hi) - max(s, lo)) * 1e-9
        u = _union(op_iv)
        if first_union is None:
            first_union = u
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
    edges = np.concatenate([[lo], first_union.ravel(), [hi]]).reshape(-1, 2)
    gaps = split_idle(edges[edges[:, 1] > edges[:, 0]], threads, top)
    return {
        "chips": len(chips),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "device_ops": [[k, float(v)] for k, v in ops.most_common(top)],
        "idle_gaps": gaps,
        "programs": progs,
    }
