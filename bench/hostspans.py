"""Host stages of the federated round loop, read from a profiler trace.

The program writes each span of its tracer (``repro.obs``) as a profiler
annotation while ``jax.profiler`` traces, so the round loop's stages lie
on the host plane beside the device's busy intervals, on one clock:

- ``prepare``: CommPru prune, broadcast, placement, cohort build;
- ``cohort_dispatch``: the launch of the cohort program;
- ``cohort.pull``: the host blocked on the cohort program and its copy to
  the host (stat ``nbytes``: the bytes it pulls);
- ``upload``: numpy deltas and the client loop, with ``client``,
  ``importance``, ``encode`` and ``fallback`` (sub-batch clients' local
  training, stats ``steps`` and ``rows``) inside;
- ``aggregate`` (pipeline FedAvg, when taken) and ``arbitrate``;
- ``evaluate``.

``readings`` turns a traced window into milliseconds of each stage per
round, megabytes pulled per round, the share of the window the stages
cover and the share of the chip's idle time that no stage explains.  It
works on ``devtrace.Trace`` objects and reads span stats where a span
carries them (``load`` gives spans that do).

Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
import heapq

import devtrace as TR

STAGES = ("prepare", "cohort_dispatch", "cohort.pull", "upload", "fallback",
          "aggregate", "arbitrate", "evaluate")
NESTED = ("client", "importance", "encode", "broadcast")
OUTER = ("bench.round", "run", "round")     # open around every stage
SPAN_NAMES = frozenset(STAGES + NESTED + OUTER)
NO_SPAN = "no span"


@dataclasses.dataclass
class StatSpan(TR.Span):
    stats: dict = dataclasses.field(default_factory=dict)


def load(path: str, names=SPAN_NAMES) -> TR.Trace:
    """``devtrace.load`` with the named host spans, each with its stats."""
    from jax.profiler import ProfileData
    tr = TR.load(path, set(names))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != TR.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.append(StatSpan(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          TR._stats(ev)))
    spans.sort(key=lambda sp: sp.start)
    tr.spans = spans
    return tr


def measure(iv) -> float:
    return sum(e - s for s, e in iv)


def union(tr: TR.Trace, names, lo: float, hi: float):
    """Merged intervals of the spans named ``names``, clipped to [lo, hi)."""
    names = {names} if isinstance(names, str) else set(names)
    return TR._merge(TR.clip([(sp.start, sp.end) for sp in tr.spans
                              if sp.name in names], lo, hi))


def minus(a, b) -> float:
    """Length of merged intervals ``a`` outside merged intervals ``b``."""
    return measure(a) - measure(_intersect(a, b))


def _intersect(a, b):
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def stat_sum(tr: TR.Trace, name: str, key: str, lo: float, hi: float
             ) -> float:
    """Sum of one stat over the spans named ``name`` that start in
    [lo, hi)."""
    return float(sum(getattr(sp, "stats", {}).get(key, 0)
                     for sp in tr.spans
                     if sp.name == name and lo <= sp.start < hi))


def idle_intervals(tr: TR.Trace, lo: float, hi: float,
                   device: int | None = None):
    """Intervals of [lo, hi) in which one chip (the first by default) ran
    no operation."""
    if not tr.busy:
        return []
    dev = device if device is not None else min(tr.busy)
    out, t = [], lo
    for s, e in TR.clip(tr.busy[dev], lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def segments(spans, lo: float, hi: float):
    """[(start, end, name)] covering [lo, hi): in each piece the innermost
    open span (the shortest that contains it) is ``name``, or ``NO_SPAN``.
    A sweep over every span boundary."""
    sps = [sp for sp in spans if sp.end > lo and sp.start < hi]
    cuts = sorted({lo, hi} | {t for sp in sps for t in (sp.start, sp.end)
                              if lo < t < hi})
    sps.sort(key=lambda sp: sp.start)
    open_: list = []                   # heap of (duration, order, span)
    out, k = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(sps) and sps[k].start <= a:
            sp = sps[k]
            heapq.heappush(open_, (sp.end - sp.start, k, sp))
            k += 1
        while open_ and open_[0][2].end <= a:
            heapq.heappop(open_)
        # a shorter span that ended is popped above; a longer one still
        # open below it stays until it surfaces
        name = open_[0][2].name if open_ else NO_SPAN
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(tr: TR.Trace, lo: float, hi: float,
                 device: int | None = None) -> dict[str, float]:
    """Idle ns of one chip in [lo, hi), by the innermost host span open
    during each piece of each idle interval (not at its midpoint)."""
    idle = idle_intervals(tr, lo, hi, device)
    out: dict[str, float] = {}
    j = 0
    for a, b, name in segments(tr.spans, lo, hi):
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        i = j
        while i < len(idle) and idle[i][0] < b:
            s, e = max(a, idle[i][0]), min(b, idle[i][1])
            if e > s:
                out[name] = out.get(name, 0.0) + (e - s)
            i += 1
    return out


def idle_unattributed_share(tr: TR.Trace, lo: float, hi: float
                            ) -> float | None:
    """Percent of the first chip's idle time in [lo, hi) during which no
    stage span is open (the innermost span is an outer one, or none)."""
    by = idle_by_span(tr, lo, hi)
    tot = sum(by.values())
    if not tot:
        return None
    return 100.0 * sum(by.get(n, 0.0) for n in OUTER + (NO_SPAN,)) / tot


def readings(tr: TR.Trace, lo: float, hi: float, rounds: int) -> dict:
    """Per window round: ms of each host stage, MB pulled, the share of
    the window the stages cover, and the unattributed idle share."""
    def ms(iv):
        return measure(iv) / 1e6 / rounds

    upload = union(tr, "upload", lo, hi)
    fallback = union(tr, "fallback", lo, hi)
    out = {
        "host_ms.prepare": ms(union(tr, "prepare", lo, hi)),
        "host_ms.wait": ms(union(tr, "cohort.pull", lo, hi)),
        "host_ms.upload": minus(upload, fallback) / 1e6 / rounds,
        "host_ms.fallback": ms(fallback),
        "host_ms.aggregate": ms(union(tr, ("aggregate", "arbitrate"),
                                      lo, hi)),
        "host_ms.eval": ms(union(tr, "evaluate", lo, hi)),
        "host_ms.dispatch": ms(union(tr, "cohort_dispatch", lo, hi)),
        "d2h_mb_per_round": stat_sum(tr, "cohort.pull", "nbytes", lo, hi)
        / 1e6 / rounds,
    }
    out["coverage_pct"] = 100.0 * measure(union(tr, STAGES, lo, hi)) \
        / (hi - lo)
    out["idle_unattributed_share"] = idle_unattributed_share(tr, lo, hi)
    return out
