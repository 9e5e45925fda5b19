"""Host spans of the program (`repro.obs`) as the per-layer metrics read
them: events of the trace's host plane matched by exact name and clipped
to the traced window."""
from __future__ import annotations

from bench.trace import _clip


def events(tr, name: str) -> list:
    """The host events named `name` inside the window."""
    return _clip([e for e in tr.host if e[0] == name], *tr.window)


def total_ms(tr, required, optional=()):
    """Summed duration of the spans named in `required` and `optional`, in
    ms; None when a name in `required` has no event in the window."""
    found = {n: events(tr, n) for n in (*required, *optional)}
    if not all(found[n] for n in required):
        return None
    return sum(e[2] for ev in found.values() for e in ev) / 1e6


def self_ms(tr, outer: str, inner: str):
    """Duration of the `outer` spans less that of the `inner` spans inside
    them, in ms; None when `outer` has no event in the window."""
    outs = events(tr, outer)
    if not outs:
        return None
    ins = [e for e in events(tr, inner)
           if any(o[1] <= e[1] and e[1] + e[2] <= o[1] + o[2] for o in outs)]
    return (sum(e[2] for e in outs) - sum(e[2] for e in ins)) / 1e6
