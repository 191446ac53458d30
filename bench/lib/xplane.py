"""Read a JAX profiler trace (``*.xplane.pb``) into the inputs of
:func:`bench.lib.trace_reduce.reduce`: per TPU device the intervals in
which an XLA operation ran, and the harness's host spans."""
from __future__ import annotations

import glob
import os
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# one event per execution of an XLA program (the harness turns off the
# per-operation line, see run.pin_devices)
OP_LINES = ("XLA Modules",)


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return paths[0]


def extract(path: str, span_names) -> tuple:
    """Returns ``(devices, spans, layout)``: {device: [(start_ns, end_ns,
    program name)]}, [(span name, start_ns, end_ns)] of the named host
    spans, and {plane: [line names]} for the record."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans, layout = {}, [], {}
    span_names = set(span_names)
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        layout[plane.name] = [line.name for line in plane.lines]
        for line in plane.lines:
            if m and line.name in OP_LINES:
                ev = devices.setdefault(f"TPU:{m.group(1)}", [])
                for e in line.events:
                    ev.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return devices, spans, layout
