"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

Everything below ``load`` works on plain tuples, so the arithmetic is
tested on small synthetic traces without a profiler:

* an *event* is ``(name, start_ns, dur_ns, text)``, ``text`` being the
  event's name and string stats joined (what kernel detection reads);
* a *window* is ``(start_ns, end_ns)``; host annotations are windows with
  a label.

Device busy time is the union of a device's op intervals inside the
window (overlapping ops count once); idle share is 1 - busy / window.
Kernel time is attributed to a layer by the host annotation that was open
when the kernel started, because the Pallas kernels of this program carry
no stable names of their own.
"""
from __future__ import annotations

import glob
import os

# Lines of a device plane that hold individual operations (TPU traces name
# it "XLA Ops"; the other lines repeat the same time as modules or steps).
OP_LINES = ("XLA Ops",)
KERNEL_MARKS = ("custom-call", "custom_call", "tpu_custom_call", "pallas",
                "mosaic")


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> dict:
    """{'devices': {plane name: [event]}, 'host': [(label, t0, t1)]} from an
    xplane file.  Host entries are every event on the host threads; callers
    pick their annotations by label."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            evs = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    text = [e.name]
                    for k, v in e.stats:
                        if isinstance(v, str):
                            text.append(f"{k}={v}")
                    evs.append((e.name, int(e.start_ns), int(e.duration_ns),
                                " ".join(text)))
            if evs:
                devices[plane.name] = top_level(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns)))
    return {"devices": devices, "host": host}


def top_level(events):
    """Drop events nested inside an earlier one (the ops a while loop or a
    fusion runs inside its own event): they add no busy time."""
    out, end = [], None
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if end is not None and ev[1] + ev[2] <= end:
            continue
        out.append(ev)
        end = ev[1] + ev[2] if end is None else max(end, ev[1] + ev[2])
    return out


def op_name(text: str) -> str:
    """``%name opcode`` from an HLO instruction as the trace names it."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text[:80]
    if rest.startswith("("):             # a tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{head} {rest.strip().split('(', 1)[0]}".strip()


def annotations(host: list, prefix: str) -> list[tuple[str, int, int]]:
    return sorted((h for h in host if h[0].startswith(prefix)),
                  key=lambda h: h[1])


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(events, lo: int, hi: int) -> int:
    return union_ns(((s, s + d) for _, s, d, _ in events), lo, hi)


def is_kernel(event) -> bool:
    text = event[3].lower()
    return any(m in text for m in KERNEL_MARKS)


def kernel_ns_in(events, windows) -> int:
    """Summed duration of kernel events that START inside any window."""
    ws = sorted((s, e) for s, e in windows)
    total = 0
    for ev in events:
        if not is_kernel(ev):
            continue
        t = ev[1]
        if any(s <= t < e for s, e in ws):
            total += ev[2]
    return total


def idle_gaps(events, lo: int, hi: int, labels, *, top: int = 10):
    """The longest stretches inside [lo, hi) with no op on this device,
    each named by the innermost host annotation open at its midpoint
    (``labels``: [(label, t0, t1)]), or "host" when none was."""
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d, _ in events)
    gaps, t = [], lo
    for s, e in ivs:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        open_ = [lab for lab in labels if lab[1] <= mid < lab[2]]
        name = min(open_, key=lambda lab: lab[2] - lab[1])[0] if open_ else "host"
        out.append((name, (e - s) / 1e9))
    return out


def top_ops(devices: dict, lo: int, hi: int, *, top: int = 10):
    """Device operations by total seconds inside [lo, hi), all devices."""
    acc: dict[str, int] = {}
    for evs in devices.values():
        for name, s, d, _ in evs:
            if lo <= s < hi:
                key = op_name(name)
                acc[key] = acc.get(key, 0) + d
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [(n, ns / 1e9) for n, ns in ranked]


def reduce(trace: dict, lo: int, hi: int, layer_prefix: str, *,
           extra_labels=()) -> dict:
    """Everything the per-layer readers take from one trace: busy seconds
    averaged over devices, kernel seconds per layer annotation (summed over
    devices), and the breakdown lists.  Idle gaps are named by the layer
    annotations (prefix stripped) and ``extra_labels`` [(name, t0, t1)]."""
    devs = trace["devices"]
    labels = annotations(trace["host"], layer_prefix)
    n = max(len(devs), 1)
    busy = sum(busy_ns(evs, lo, hi) for evs in devs.values()) / n / 1e9
    kernel_s: dict[str, float] = {}
    for lab in {lab[0] for lab in labels}:
        wins = [(s, e) for name, s, e in labels if name == lab]
        kernel_s[lab] = sum(kernel_ns_in(evs, wins)
                            for evs in devs.values()) / 1e9
    named = [(n[len(layer_prefix):], s, e) for n, s, e in labels]
    named += list(extra_labels)
    gaps: list = []
    for evs in devs.values():
        gaps.extend(idle_gaps(evs, lo, hi, named))
    gaps.sort(key=lambda g: -g[1])
    return {
        "devices": len(devs),
        "busy_s": busy,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": kernel_s,
        "device_ops": top_ops(devs, lo, hi),
        "idle_gaps": gaps[:10],
    }
