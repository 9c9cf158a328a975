"""Reduce one profiler trace (``.xplane.pb``) to the numbers the per-layer
readers and the result's ``device`` and ``breakdown`` take.

Planes whose name starts with ``/device:`` and that hold an ``XLA Ops``
line are the chips. On each, the ``XLA Ops`` line holds one event per device operation
and the ``XLA Modules`` line one per program execution. The host plane's
threads hold the spans the harness records around the calls into each
layer (names starting ``bench.``); ``bench.window`` spans the measured
window, and everything is clipped to it.

- busy: the union of device-op intervals in the window, averaged over the
  chips; idle share is 1 - busy / window;
- ``device_ops``: device time per operation name, largest first;
- ``idle_gaps``: the idle time of the first chip, split by the ``bench.``
  spans that covered each gap's midpoint on any host thread ("none" where
  the host was in none of them);
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
SPAN_PREFIX = "bench."
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


def _covering(spans: Dict[str, np.ndarray], points: np.ndarray
              ) -> List[str]:
    """For each point, the '+'-joined sorted names of the spans (any
    thread) that cover it, or 'none'."""
    hit = {name: np.zeros(points.size, bool) for name in spans}
    for name, iv in spans.items():
        iv = iv[np.argsort(iv[:, 0], kind="stable")]
        run_end = np.maximum.accumulate(iv[:, 1])
        j = np.searchsorted(iv[:, 0], points, side="right") - 1
        ok = j >= 0
        hit[name][ok] = run_end[j[ok]] >= points[ok]
    labels = []
    for p in range(points.size):
        names = sorted(n[len(SPAN_PREFIX):] for n in spans if hit[n][p])
        labels.append("+".join(names) if names else "none")
    return labels


def reduce_trace(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, spans = [], collections.defaultdict(list)
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and any(
                line.name == "XLA Ops" for line in plane.lines):
            chips.append(plane)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    spans = {k: np.asarray(v, np.float64) for k, v in spans.items()}
    win = spans.pop(WINDOW, None)
    if win is None or not chips:
        return {"chips": len(chips), "window_s": None, "busy_s": None}
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
    gaps = []
    edges = np.concatenate([[lo], first_union.ravel(), [hi]]).reshape(-1, 2)
    gap_iv = edges[edges[:, 1] > edges[:, 0]]
    if gap_iv.size:
        labels = _covering(spans, gap_iv.mean(1))
        by = collections.Counter()
        for lab, (s, e) in zip(labels, gap_iv):
            by[lab] += (e - s) * 1e-9
        gaps = [[k, float(v)] for k, v in by.most_common(top)]
    return {
        "chips": len(chips),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "device_ops": [[k, float(v)] for k, v in ops.most_common(top)],
        "idle_gaps": gaps,
        "programs": progs,
    }
