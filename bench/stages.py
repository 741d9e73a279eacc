#!/usr/bin/env python3
"""Where a federated cell's round goes, stage by stage.

  python3 bench/stages.py --workload <name> --seed <n> --seconds <s>
      [--mode traced|obs] [--checks 0|1]

Runs the cell once as ``bench/run.py`` does, on the chip it finds, and
prints one JSON line:

- ``traced``: the tracer and the profiler on, as ``--trace 1``.  Besides
  the cell's per-layer metrics and breakdown, whose idle gaps are now
  named by the program's stage spans, the line carries
  ``hostspans.readings`` (ms of each host stage per round, MB pulled per
  round, the window's coverage by stages, the unattributed idle share)
  and the chip's idle seconds by innermost span;
- ``obs``: the tracer on and the profiler off, so that on one seed the
  traced ``round_s`` less this one is the profiler's own cost.

The untraced run is ``bench/run.py --trace 0``.  In both modes the line
also gives the calls and seconds of the host work that exists only for
the tracer's readers (drift dispersion, the rank trajectory, memory
samples, shape signatures).  ``--checks 0`` leaves out the comparison with
the reference, and with it ``correct``.  ``--smoke`` runs the cell at its
smoke sizes on whatever JAX finds (the CPU in the tests).

This is a second entry point beside ``run.py`` only until the harness
reads the stage spans itself: ``make_ctx`` repeats the ``Ctx`` that
``run.main`` builds inline, and ``capture_stages`` swaps ``fed.py``'s span
filter and trace loader at run time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402  (starts the set-up clock)
import common as C  # noqa: E402
import devtrace as TR  # noqa: E402
import fed  # noqa: E402
import hostspans as HS  # noqa: E402

READER_WORK = (("repro.fedsim.pipeline", "UploadPipeline", "_emit_drift"),
               ("repro.obs.record", "RunRecorder", "record_ranks"),
               ("repro.obs.profile", None, "sample_memory"),
               ("repro.obs.profile", None, "shape_signature"))


def time_reader_work() -> dict:
    """Wrap each function of ``READER_WORK`` in a timer; returns the live
    {name: {"calls", "s"}} table."""
    import importlib
    table = {}
    for mod, cls, fn in READER_WORK:
        owner = importlib.import_module(mod)
        if cls is not None:
            owner = getattr(owner, cls)
        real = getattr(owner, fn)
        acc = table[fn] = {"calls": 0, "s": 0.0}

        def timed(*a, _real=real, _acc=acc, **kw):
            t = time.perf_counter()
            try:
                return _real(*a, **kw)
            finally:
                _acc["calls"] += 1
                _acc["s"] += time.perf_counter() - t
        setattr(owner, fn, timed)
    return table


def capture_stages(box: dict) -> None:
    """Have ``fed.py`` keep the program's stage spans, with their
    stats, in the trace it reduces, and hand that reduction to ``box``."""
    fed.SPANS = set(fed.SPANS) | HS.SPAN_NAMES
    real = fed._layer_ctx

    def layer_ctx(ctx, spec, traffic, data, seed, tdir, st, setup_rounds):
        lc = real(ctx, spec, traffic, data, seed, tdir, st, setup_rounds)
        lc["trace"].spans = HS.load(TR.find_xplane(tdir.name)).spans
        box["lc"] = lc
        return lc
    fed._layer_ctx = layer_ctx


def make_ctx(args) -> R.Ctx:
    if args.smoke:
        return R.smoke_ctx(args.workload, args.seed, args.seconds,
                           args.mode == "traced")
    bench = C.benchmark()
    cell = C.find_cell(bench, args.workload)
    dev = R.device_info(cell["chips"])
    C.use_program()
    from repro.compat import enable_compilation_cache
    enable_compilation_cache()
    return R.Ctx(bench=bench, cell=cell,
                 spec=C.config_file(bench, cell["config"]),
                 traffic=C.traffic_file(cell["traffic"]), seed=args.seed,
                 seconds=args.seconds, trace=args.mode == "traced",
                 t_start=R.T_START, clock=C.CompileClock(),
                 ref=C.reference_module(bench, cell["config"]),
                 peak=R.peak_for(dev["kind"]), device=dev)


def measure(args) -> dict:
    ctx = make_ctx(args)
    C.use_program()
    from repro import obs as OBS
    work = time_reader_work()
    box: dict = {}
    if args.mode == "traced":
        capture_stages(box)
    if not args.checks:
        fed._checks = lambda *a, **kw: []
    if args.mode == "obs":
        OBS.configure(path=None, enabled=True, health=False, profile=False)
    try:
        res = R.execute(ctx)
    finally:
        OBS.disable()
    win = res.pop("_window")
    out = {"workload": args.workload, "seed": args.seed, "mode": args.mode,
           "device": res["device"], "window": win,
           "rounds_run": ctx.traffic["setup_rounds"] + win["rounds"],
           "round_s": win["seconds"] / win["rounds"],
           "reader_work": work, "metrics": res["metrics"]}
    if args.checks:
        out["correct"] = res["correct"]
        out["checks"] = res["checks"]
    if args.mode != "traced":
        return out
    lc = box["lc"]
    tr, lo, hi = lc["trace"], lc["lo"], lc["hi"]
    out["stages"] = HS.readings(tr, lo, hi, lc["rounds"])
    by = HS.idle_by_span(tr, lo, hi)
    out["idle_s_by_span"] = sorted(((k, v / 1e9) for k, v in by.items()),
                                   key=lambda kv: -kv[1])
    out["breakdown"] = res.get("breakdown")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("traced", "obs"), default="traced")
    ap.add_argument("--checks", type=int, choices=(0, 1), default=1)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    try:
        out = measure(args)
    except SystemExit as e:
        C.log(f"bench/stages.py: {e}; nothing was run")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
