#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once: set up, warm, measure for --seconds, print
one line, exit. It needs a TPU and as many chips as the cell asks for;
without them it exits non-zero and prints no line. It has no CPU mode.
The last line has passed harness/line.py's validator; a line that fails
is not printed, the reason is, and the exit code is non-zero.
"""

import time

_T_PROCESS = time.perf_counter()

import argparse   # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="keep the profiler's trace in DIR")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        from benchmark.harness.manifest import Manifest
        from benchmark.harness import line as line_mod
        manifest = Manifest(ROOT)
        cell = manifest.cell(args.workload)
        import stellar_core_tpu  # noqa: F401  (the system under test)
    except (ImportError, OSError, KeyError) as e:
        print("benchmark/run.py: %s: %s" % (type(e).__name__, e),
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("benchmark/run.py: cell %s needs %d TPU chip(s); JAX "
              "resolved platform %r (%s x%d). Nothing was run."
              % (args.workload, cell["chips"], devs[0].platform,
                 devs[0].device_kind, len(devs)), file=sys.stderr)
        return 2
    from stellar_core_tpu.parallel.device import configure_compile_cache
    configure_compile_cache()
    import logging
    from stellar_core_tpu.util.log import init_logging
    init_logging(logging.WARNING)
    from benchmark.harness import runner
    try:
        res = runner.run_cell(manifest, args.workload, args.seed,
                              args.seconds, bool(args.trace), _T_PROCESS,
                              keep_trace=args.keep_trace)
        sys.stdout.flush()
        runner.print_compared(res["line"]["compared"])
        line_mod.emit(res["line"], res["expected"], bool(args.trace))
    except (runner.RunError, line_mod.LineError, TimeoutError,
            ValueError, KeyError) as e:
        print("benchmark/run.py: no result: %s: %s"
              % (type(e).__name__, e), file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
