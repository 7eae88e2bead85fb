"""The program's own stages in a profiler trace.

The scheduler daemon annotates its stages on the profiler's clock
(``koord.*`` TraceAnnotations, koordinator_tpu/obs/spans.py): the
client's Sync and its request build, the server's Sync body, decode,
apply, scatter and publish, the Assign body, its queue, dispatch,
readback and reply.  This module reads them beside the benchmark's own
``bench.*`` spans and the device operations that ``tracefile`` reads,
and reduces them with plain interval arithmetic, so that every PR
computes the same number the same way (``benchmark/tests/
test_stages.py`` checks each number by hand):

* ``span_stats``: per span name, the count and the total seconds of the
  spans that start inside the window;
* ``idle_spans``: the device's idle time cut at every span edge, each
  piece charged to the innermost ``bench.*`` or ``koord.*`` span that
  covers it (``"none"`` where none does);
* ``stage_ms``: the per-cycle means of the stages, in ms.

A trace of a program without the annotations holds no ``koord.*``
span: its stage means are absent, not zero.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from benchmark.harness import tracefile as tf

PREFIXES = ("bench.", "koord.")
WINDOW = "bench.window"
# the client-side RPC spans whose idle the program's stages must name
RPC_SPANS = ("bench.sync", "bench.assign")

# per-layer means (ms per cycle): metric -> the span it sums
STAGE_SPANS = {
    "sync_encode_ms": "koord.client.sync_encode",
    "sync_decode_ms": "koord.sync_decode",
    "sync_apply_ms": "koord.sync_apply",
    "sync_publish_ms": "koord.sync_publish",
    "assign_queue_ms": "koord.assign_queue",
    "assign_dispatch_ms": "koord.dispatch",
    "assign_reply_ms": "koord.assign_reply",
}
# a parent span and the children that should cover it
CHILDREN = {
    "koord.sync": ("koord.sync_decode", "koord.sync_apply",
                   "koord.sync_publish"),
    "koord.assign": ("koord.assign_queue", "koord.dispatch",
                     "koord.readback", "koord.assign_reply"),
}


def host_spans(path: str) -> List[tf.Event]:
    """The ``bench.*`` and ``koord.*`` host events of the trace at
    ``path``, as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    out: List[tf.Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events
                           if e.name.startswith(PREFIXES))
    return out


def span_stats(spans: Sequence[tf.Event], lo: float, hi: float):
    """{name: [count, seconds]} of the spans that start in [lo, hi)."""
    by: Dict[str, list] = {}
    for s, e, name in spans:
        if lo <= s < hi:
            row = by.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += (e - s) / 1e9
    return by


def idle_pieces(intervals: Sequence[tf.Event], spans: Sequence[tf.Event],
                lo: float, hi: float):
    """(label, seconds, inside an RPC span) for every piece of the
    device's idle time in [lo, hi] between two span edges; the label is
    the innermost (shortest) covering span, ``"none"`` where none
    covers the piece."""
    spans = [(max(s, lo), min(e, hi), e - s, name)
             for s, e, name in spans
             if name != WINDOW and e > lo and s < hi and e > s]
    gaps, t = [], lo
    for s, e in tf.merged(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    edges = sorted({b for g in gaps for b in g}
                   | {b for s, e, _, _ in spans for b in (s, e)})
    starts = sorted(spans, key=lambda x: x[0])
    active: List[tuple] = []
    out = []
    i = g = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(starts) and starts[i][0] <= a:
            active.append(starts[i])
            i += 1
        active = [x for x in active if x[1] > a]
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps) or gaps[g][0] > a:
            continue  # the device is busy over [a, b)
        label = min((d, name) for _, _, d, name in active)[1] \
            if active else "none"
        rpc = any(name in RPC_SPANS for _, _, _, name in active)
        out.append((label, (b - a) / 1e9, rpc))
    return out


def idle_spans(intervals: Sequence[tf.Event], spans: Sequence[tf.Event],
               lo: float, hi: float, n: int = 10):
    """[[span, seconds]]: the device's idle time in [lo, hi] charged,
    piece by piece, to the innermost span covering it; the top ``n``."""
    by: Dict[str, float] = {}
    for label, secs, _ in idle_pieces(intervals, spans, lo, hi):
        by[label] = by.get(label, 0.0) + secs
    ranked = sorted(by.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[k, v] for k, v in ranked]


def rpc_idle_named(intervals, spans, lo, hi):
    """(idle seconds inside a ``bench.sync`` or ``bench.assign`` span,
    the part of it charged to a ``koord.*`` span)."""
    inside = named = 0.0
    for label, secs, rpc in idle_pieces(intervals, spans, lo, hi):
        if rpc:
            inside += secs
            if label.startswith("koord."):
                named += secs
    return inside, named


def stage_ms(stats: Dict[str, list], cycles: int) -> Dict[str, float]:
    """The per-cycle stage means (ms) the trace holds spans for."""
    if cycles <= 0:
        return {}
    total = {name: row[1] for name, row in stats.items()}
    out = {m: total[span] * 1e3 / cycles
           for m, span in STAGE_SPANS.items() if span in total}
    wire = ("koord.client.sync", "koord.client.sync_encode", "koord.sync")
    if all(name in total for name in wire):
        out["sync_wire_ms"] = (total[wire[0]] - total[wire[1]]
                               - total[wire[2]]) * 1e3 / cycles
    return out


def coverage(stats: Dict[str, list]) -> Dict[str, float]:
    """Per parent span: its children's summed time over its own."""
    out = {}
    for parent, kids in CHILDREN.items():
        if stats.get(parent, [0, 0.0])[1] > 0:
            out[parent] = sum(stats.get(k, [0, 0.0])[1] for k in kids) \
                / stats[parent][1]
    return out
