"""From a profiler trace to the benchmark's per-layer numbers.

:func:`load` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
two lists, on the profiler's one clock:

* ``ops``: every device operation of the ``XLA Ops`` line of each TPU
  plane, with its chip, name, module and :func:`op_kind`; an op that
  encloses others on its chip (a ``while`` around its body) is of kind
  ``outer``, so that no device time is counted twice;
* ``spans``: the harness's own host spans (``bench.*``), recorded with
  ``jax.profiler.TraceAnnotation``.

The traced window runs from the start of the first ``bench.call`` span
to the end of the last ``bench.readback`` span; every reduction below
clips to it. Times are in nanoseconds inside a trace and in seconds
outside.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
#: Modules the harness itself runs (readback, state build); their ops
#: are device work of the benchmark, not of the program's launches.
HARNESS_MODULE = "jit_bench_"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all")


def op_kind(name: str, category: str, long_name: str, module: str,
            tf_op: str = "") -> str:
    """``harness``, ``kernel`` (a Pallas/Mosaic custom call),
    ``collective`` or ``other`` (the launch glue: copies, concatenates,
    slices, loop bookkeeping)."""
    if module.startswith(HARNESS_MODULE):
        return "harness"
    text = f"{name} {category} {long_name}"
    if "custom-call" in text or "custom_call" in text \
            or tf_op.endswith("pallas_call"):
        return "kernel"
    if any(c in text for c in COLLECTIVES):
        return "collective"
    return "other"


def mark_outer(ops: list) -> None:
    """Give each op that encloses another op of its chip the kind
    ``outer``: only the innermost ops are counted by kind."""
    by_chip = defaultdict(list)
    for op in ops:
        by_chip[op["chip"]].append(op)
    for chip_ops in by_chip.values():
        chip_ops.sort(key=lambda o: (o["start_ns"], -o["dur_ns"]))
        stack = []
        for op in chip_ops:
            while stack and stack[-1]["start_ns"] + stack[-1]["dur_ns"] \
                    <= op["start_ns"]:
                stack.pop()
            if stack and op["start_ns"] + op["dur_ns"] <= \
                    stack[-1]["start_ns"] + stack[-1]["dur_ns"]:
                stack[-1]["kind"] = "outer"
            stack.append(op)


def check_kernel_found(ops: list) -> None:
    """A device trace with ops but none of them the kernel means the
    kernel is no longer recognised: fail, rather than read its time as
    0 and as launch glue."""
    if ops and not any(op["kind"] == "kernel" for op in ops):
        names = sorted({op["name"] for op in ops})[:20]
        raise ValueError("the device trace holds ops but no kernel op "
                         f"(op_kind found none among {names})")


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _chip(plane_name: str) -> int | None:
    rest = plane_name[len(DEVICE_PLANE):]
    return int(rest) if plane_name.startswith(DEVICE_PLANE) and \
        rest.isdigit() else None


def load(profile_dir, steps_per_call: int) -> dict:
    """The trace under ``profile_dir`` as ``{"ops", "spans",
    "steps_per_call"}``."""
    from jax.profiler import ProfileData

    files = sorted(Path(profile_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans = [], []
    for plane in data.planes:
        chip = _chip(plane.name)
        if chip is not None:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (ev.start_ns, ev.end_ns, ev.name)
                for ev in (lines[MODULES_LINE].events
                           if MODULES_LINE in lines else ()))
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                st = _stats(ev)
                module = str(st.get("hlo_module", "")) or next(
                    (n for s, e, n in modules
                     if s <= ev.start_ns < e), "")
                category = str(st.get("hlo_category", ""))
                long_name = str(st.get("long_name", ""))[:200]
                tf_op = str(st.get("tf_op", ""))
                ops.append({
                    "chip": chip, "name": ev.name, "module": module,
                    "start_ns": ev.start_ns, "dur_ns": ev.duration_ns,
                    "category": category, "long_name": long_name,
                    "tf_op": tf_op[-200:],
                    "kind": op_kind(ev.name, category, long_name, module,
                                    tf_op),
                })
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append({"name": ev.name,
                                      "start_ns": ev.start_ns,
                                      "dur_ns": ev.duration_ns})
    mark_outer(ops)
    check_kernel_found(ops)
    spans.sort(key=lambda s: s["start_ns"])
    return {"ops": ops, "spans": spans, "steps_per_call": steps_per_call}


# ---- reductions -----------------------------------------------------------


def window(trace: dict) -> tuple[float, float] | None:
    calls = [s for s in trace["spans"] if s["name"] == "bench.call"]
    backs = [s for s in trace["spans"] if s["name"] == "bench.readback"]
    if not calls or not backs:
        return None
    return (calls[0]["start_ns"],
            max(s["start_ns"] + s["dur_ns"] for s in backs))


def window_s(trace: dict) -> float:
    w = window(trace)
    return 0.0 if w is None else (w[1] - w[0]) * 1e-9


def steps(trace: dict) -> int:
    """Steps the traced window advanced: calls times steps per call."""
    calls = sum(s["name"] == "bench.call" for s in trace["spans"])
    return calls * trace["steps_per_call"]


def _clipped(trace: dict):
    """Ops of the window, clipped to it: ``(op, start, end)``."""
    w = window(trace)
    if w is None:
        return
    for op in trace["ops"]:
        s = max(op["start_ns"], w[0])
        e = min(op["start_ns"] + op["dur_ns"], w[1])
        if e > s:
            yield op, s, e


def device_s(trace: dict, kind: str, chips: int) -> float | None:
    """Device seconds of ops of ``kind`` in the window, per chip; None
    where the trace holds no device op at all."""
    if not trace["ops"]:
        return None
    total = sum(e - s for op, s, e in _clipped(trace) if op["kind"] == kind)
    return total * 1e-9 / chips


def _busy_intervals(trace: dict) -> dict:
    """Per chip, the union of its op intervals in the window."""
    by_chip = defaultdict(list)
    for op, s, e in _clipped(trace):
        by_chip[op["chip"]].append((s, e))
    merged = {}
    for chip, iv in by_chip.items():
        iv.sort()
        out = [list(iv[0])]
        for s, e in iv[1:]:
            if s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        merged[chip] = out
    return merged


def busy_s(trace: dict, chips: int) -> float:
    """Seconds in which some op ran, averaged over the chips."""
    merged = _busy_intervals(trace)
    return sum(e - s for iv in merged.values() for s, e in iv) * 1e-9 / chips


def _span_at(trace: dict, t: float) -> str:
    """The innermost harness span open at ``t``."""
    open_ = [s for s in trace["spans"]
             if s["start_ns"] <= t < s["start_ns"] + s["dur_ns"]]
    return max(open_, key=lambda s: s["start_ns"])["name"] if open_ \
        else "no span"


def idle_gaps(trace: dict) -> list[tuple[str, int, float]]:
    """Every idle gap in the window: ``(host span open, chip, seconds)``,
    longest first."""
    w = window(trace)
    gaps = []
    for chip, iv in _busy_intervals(trace).items():
        edges = [(w[0], w[0])] + [tuple(x) for x in iv] + [(w[1], w[1])]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((_span_at(trace, e0), chip, (s1 - e0) * 1e-9))
    return sorted(gaps, key=lambda g: -g[2])


def breakdown(trace: dict, chips: int, top: int = 10) -> dict:
    """The device ops that took most time (per chip) and the longest
    idle gaps, named by the host span open at the time."""
    per_op = defaultdict(float)
    for op, s, e in _clipped(trace):
        if op["kind"] == "outer":
            continue
        per_op[f"{op['kind']} {op['name']}"] += (e - s) * 1e-9 / chips
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = [[f"{span} (chip {chip})", sec]
            for span, chip, sec in idle_gaps(trace)[:top]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}
