"""Reduce a profiler trace of the measured window to per-layer numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but JAX). Planes named
``/device:<platform>:<n>`` are devices; the host plane carries the
benchmark's own spans as trace annotations ``bench:<name>``, on the same
clock.

Output (``reduce``), all times in seconds:

* ``window_s``: the traced window, from the host span ``bench:window``
  (the whole trace when it is absent), ending where the devices stopped
  recording if they did (``covered``; ``cut`` says whether they did,
  ``full_window_s`` is the window before the cut);
* ``host_n``: how many of each ``bench:<name>`` span start inside it;
* ``busy_s``: per device, the union of the intervals in which a program
  ran (the ``XLA Modules`` line), inside the window, averaged over the
  devices. Programs and not single operations: a program's while loop
  shows as thousands of short operations with the loop's own control
  between them;
* ``modules``: device time and launch count per XLA program, by the
  program's name (``jit__build_planes`` -> ``_build_planes``).
  A program's time starts where the one before it ended, if that is
  later than its own start: the device runs one program at a time, so
  an overlap is time it waited in the queue;
* ``ops``: device time and count per operation name, leaving out the
  control-flow containers (``while``, ``cond``, ``call``) that hold
  other operations; ``kernels`` the same for the Mosaic kernels named in
  ``KERNELS``; ``dropped`` whether the device said it dropped events;
* ``host``: each ``bench:<name>`` span's durations;
* ``idle_gaps``: idle time on the devices inside the window, summed by
  the benchmark span that was open at the middle of each gap (``other``
  where none was), longest first.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

KERNELS = ("sketch_insert_walk", "cell_decode")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench:"
DISPATCH = ("ingest", "flush")  # host spans that put work on the device
CONTAINER = re.compile(r"^(while|cond|conditional|call)(\.\d+)?$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(name: str) -> str:
    """``jit__ingest_stacked_lsketch(123)`` -> ``_ingest_stacked_lsketch``."""
    name = re.sub(r"\(.*\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def union_length(iv: np.ndarray) -> float:
    """Total length of the union of ``[start, end]`` rows."""
    if len(iv) == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    starts = iv[:, 0]
    # a new run starts where an interval begins after every earlier end
    new = np.ones(len(iv), bool)
    new[1:] = starts[1:] > ends[:-1]
    run_id = np.cumsum(new) - 1
    run_start = starts[new]
    run_end = np.zeros(len(run_start))
    np.maximum.at(run_end, run_id, ends)
    return float(np.sum(run_end - run_start))


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The idle ``[start, end]`` gaps of ``[lo, hi]`` not covered by ``iv``."""
    if len(iv) == 0:
        return np.array([[lo, hi]])
    iv = iv[np.argsort(iv[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    g_start = np.concatenate([[lo], ends])
    g_end = np.concatenate([iv[:, 0], [hi]])
    g_start = np.maximum(g_start, lo)
    g_end = np.minimum(g_end, hi)
    keep = g_end > g_start
    return np.stack([g_start[keep], g_end[keep]], 1)


def op_name(name: str) -> str:
    """An op event is named by its HLO text; keep the instruction's name
    (``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9


def load(path: str) -> dict:
    """The raw events: per device plane its module and op events, and the
    host's benchmark spans, as ``(name, start_s, dur_s)`` lists."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "dropped": False}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name \
                and "CUSTOM" not in plane.name:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    dev["modules"] += list(_events(line))
                elif line.name in OP_LINES:
                    dev["ops"] += [(op_name(n), t, d)
                                   for n, t, d in _events(line)]
                elif any("Dropped" in n for n, _, _ in _events(line)):
                    out["dropped"] = True
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, t, dur in _events(line):
                    if name.startswith(HOST_PREFIX):
                        out["host"].append((name[len(HOST_PREFIX):], t, dur))
    return out


def covered(devs: dict, host: list, lo: float, hi: float):
    """The part of ``[lo, hi]`` up to where a device line stopped recording.

    A TPU's trace buffer holds a bounded number of events. A window of
    millions of operations outruns it: the device then records nothing
    more and marks the rest with one ``Trace Buffers Dropped`` event (one
    v5e chip stopped after 5.03 million operations, 5.45 s into a 10.2 s
    window of ``comfs_ingest``). Each dispatching host span
    (``DISPATCH``) puts work on the device, so where two or more of them
    start after a device line's last recorded event ends, the line was
    cut there, and the window ends where it stopped. Returns
    ``(hi, cut)``."""
    lasts = []
    for dv in devs.values():
        for evs in (dv["modules"], dv["ops"]):
            ends = [t + d for _, t, d in evs if t + d >= lo and t <= hi]
            if ends:
                lasts.append(max(ends))
    if not lasts:
        return hi, False
    last = min(lasts)
    after = sum(last < t < hi for n, t, _ in host if n in DISPATCH)
    return (last, True) if after >= 2 else (hi, False)


def reduce(raw: dict) -> dict:
    host = raw["host"]
    win = [(t, t + d) for n, t, d in host if n == "window"]
    devs = raw["devices"]
    if win:
        lo, hi = win[0]
    else:
        ts = [t for dv in devs.values() for _, t, _ in dv["ops"]]
        te = [t + d for dv in devs.values() for _, t, d in dv["ops"]]
        lo, hi = (min(ts), max(te)) if ts else (0.0, 0.0)
    full_s = hi - lo
    hi, cut = covered(devs, host, lo, hi)
    inner = sorted((t, t + d, n) for n, t, d in host if n != "window")
    sp_start = np.array([s for s, _, _ in inner])
    sp_end = np.array([e for _, e, _ in inner])
    sp_name = np.array([n for _, _, n in inner] + ["other"])
    modules, ops, kernels = {}, {}, {}
    busy, idle = [], {}
    for dv in devs.values():
        iv = []
        done = lo  # the device runs one program at a time
        for name, t, d in sorted(dv["modules"], key=lambda e: e[1]):
            if t + d < lo or t > hi:
                continue
            a, b = max(t, done), min(t + d, hi)
            done = max(done, b)
            m = modules.setdefault(module_name(name), [0.0, 0])
            m[0] += max(b - a, 0.0)
            m[1] += 1
            iv.append((max(t, lo), min(t + d, hi)))
        for name, t, d in dv["ops"]:
            if t + d < lo or t > hi or CONTAINER.match(name):
                continue
            dd = min(t + d, hi) - max(t, lo)
            o = ops.setdefault(name, [0.0, 0])
            o[0] += dd
            o[1] += 1
            for kname in KERNELS:
                if kname in name:
                    k = kernels.setdefault(kname, [0.0, 0])
                    k[0] += dd
                    k[1] += 1
            if not dv["modules"]:
                iv.append((max(t, lo), min(t + d, hi)))
        iv = np.array(iv, np.float64).reshape(-1, 2)
        busy.append(union_length(iv))
        g = gaps(iv, lo, hi)
        mid = g.mean(axis=1)
        j = np.searchsorted(sp_start, mid, side="right") - 1
        open_ = (j >= 0) & (sp_end[np.maximum(j, 0)] >= mid) if len(inner) \
            else np.zeros(len(g), bool)
        who = sp_name[np.where(open_, j, len(inner))]
        for n in np.unique(who):
            idle[str(n)] = idle.get(str(n), 0.0) + float(
                np.sum(g[who == n, 1] - g[who == n, 0])) / max(len(devs), 1)
    host_times, host_n = {}, {}
    for n, t, d in host:
        host_times.setdefault(n, []).append(d)
        if lo <= t < hi:
            host_n[n] = host_n.get(n, 0) + 1
    return {"window_s": hi - lo, "full_window_s": full_s, "cut": cut,
            "busy_s": float(np.mean(busy)) if busy else 0.0,
            "n_devices": len(devs),
            "modules": modules, "ops": ops, "kernels": kernels,
            "dropped": bool(raw.get("dropped", False)),
            "host": host_times, "host_n": host_n,
            "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])}


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the device operations
    that took most time and the idle time by host activity."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[n, v[0]] for n, v in ops],
            "idle_gaps": [[n, s] for n, s in red["idle_gaps"][:top]]}
