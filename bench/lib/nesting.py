"""Operations that run inside other operations of one device.

A loop runs as one operation whose event spans all of its iterations,
and the device trace may list the operations of the loop's body as
events of their own inside it. A reader that sums the operations under a
scope would then count the body's time twice: once in the loop (which
runs the body's instructions, so falls under the body's scopes) and once
in the body's own events. ``outermost`` keeps, on each device, only the
events that no other event of that device contains, so every instant of
the window is counted once, as ``busy_s`` counts it.
"""
from __future__ import annotations

import dataclasses

from bench.lib import trace as tr


def outermost(events: list[tr.Event]) -> list[tr.Event]:
    """The events that lie inside no other event of the list."""
    keep, reach = [], float("-inf")
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        if e.end_ns <= reach:
            continue  # inside an event already kept
        keep.append(e)
        reach = e.end_ns
    return keep


def outermost_view(view: tr.TraceView) -> tr.TraceView:
    """``view`` with each device's operations cut to the outermost."""
    return dataclasses.replace(view, ops=[outermost(dev) for dev in view.ops])
